"""Hot-swap: the pieces whose host sums the flip gate was handed from the
piece's commit (%): ``HotSwapResult.stats["host_sums_carried"]``, the delta
jobs' sums of the bytes they wrote, over the pieces the gate compared (the
``swap_verify`` span's ``piece``); the rest the swap read back from the store
and summed before the gate. Median per operation. A program without the
count reads nothing."""

from layers import swap_events


def share(op):
    swap = getattr(op, "swap", None)
    pieces = [piece for _, event, piece, _ in op.flight
              if event == "swap_verify"]
    if not swap or "host_sums_carried" not in swap["stats"] \
            or not pieces or pieces[-1] <= 0:
        return None
    return 100.0 * swap["stats"]["host_sums_carried"] / pieces[-1]


def read(run):
    return swap_events.median(share(op) for op in run.ops)
