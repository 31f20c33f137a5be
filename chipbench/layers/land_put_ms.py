"""Store read-back + host staging: the landing thread's time inside
``jax.device_put(stack, device)`` as the host sees the call (``sink_put``
spans), summed per operation, median per operation (ms)."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_put")
