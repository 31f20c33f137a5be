"""Hot-swap: the manifests fetched over the fabric or built from the store,
``plan_delta`` and ``plan_swap``: the summed ms of an operation's ``swap_plan``
spans (the resolver's and the device half's), median per operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "swap_plan")
