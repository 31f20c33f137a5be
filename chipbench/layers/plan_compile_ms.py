"""Assemble+checksum: backend-compile time inside the assembly
(``sink_compile``, stamped only by an assembly whose plan was new), summed
per operation, MEAN over the window's operations (ms): how many
operations of a window meet a new plan is chance, and the mean times the
operations is what the window paid. 0.0 where assemblies ran and none
compiled; nothing where the program stamps no assembly at all."""

from layers import sink_events


def read(run):
    assembled = [op for op in run.ops
                 if sink_events.summed_ms(op, "sink_assemble") is not None]
    if not assembled:
        return None
    return sum(sink_events.summed_ms(op, "sink_compile") or 0.0
               for op in assembled) / len(assembled)
