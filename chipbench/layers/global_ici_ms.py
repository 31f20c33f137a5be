"""Sharded landing: device time of the fan-out's programs per operation (ms),
on the chip's plane where they took longest: ``ici_ms``'s reading (its
programs, its walk over the chips' planes), under a name of this cell's own
because that metric's list of cells is the benchmark's."""

from layers import ici_ms


def read(run):
    return ici_ms.read(run)
