"""Sharded landing: what a ranged task costs beside its landing: its time
inside ``download_to_device`` (``device_pull``: admitted -> the sink in hand)
less the landing thread's spans in it (``sink_wait``, ``sink_finalize``,
``sink_replicate``, ``sink_verify_chips``), the median over an operation's
ranged tasks (the header's too), then the median per operation (ms). ROADMAP
S12's reading with no origin, scheduler or transfer in it; 60-odd tasks an
operation multiply it, three at a time."""

from layers import global_events


def of_operation(op):
    return global_events.median(
        global_events.fixed_ms(task["flight"])
        for task in global_events.rows(op))


def read(run):
    return global_events.median_per_operation(run, of_operation)
