"""Sharded landing: the sinks' assemblies against the memory roofline (%),
on the chip whose assemblies took longest: the least time that chip could
take, one read and one write of the bytes that LANDED ON IT in the operation
(``assemble_roofline.least_seconds``; the ranged tasks whose landing chip it
is, ``op.ranged``) at the peak HBM rate, over the device time of the assembly
programs on that chip's plane. ``assemble_roofline`` holds chip 0's plane
against the whole operation's bytes, which is right where chip 0 lands them
all and counts four chips' bytes against one chip's time here."""

from layers import assemble_ms, assemble_roofline, global_events


def landed_by_chip(run) -> dict:
    """chip id -> bytes landed on it, an operation."""
    out: dict = {}
    for op in run.ops:
        for task in global_events.rows(op):
            chip = task["chips"][0]
            out[chip] = out.get(chip, 0) + task["end"] - task["start"]
    return {chip: total / len(run.ops) for chip, total in out.items()}


def read(run):
    import reduce_trace as trace

    if run.trace is None or not run.ops:
        return None
    landed = landed_by_chip(run)
    slowest = None
    for plane in trace.chip_planes(run.trace):
        chip = int(plane.rsplit(":", 1)[-1])
        runs = [(s, s + d) for name, s, d in
                trace.events_on(run.trace, plane, trace.MODULE_LINES)
                if any(n in name for n in assemble_ms.PROGRAMS)]
        took = sum(e - s for s, e in trace.clip(runs, run.windows)) \
            / len(run.ops)
        if took > 0 and landed.get(chip) and (slowest is None
                                              or took > slowest[0]):
            slowest = (took, landed[chip])
    if slowest is None:
        return None
    return 100.0 * assemble_roofline.least_seconds(slowest[1], run.peaks) \
        / slowest[0]
