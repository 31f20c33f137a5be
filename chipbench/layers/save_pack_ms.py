"""Checkpoint save / resume: summed device time of the programs that build
the file's words from the typed tensors and checksum them
(``_save_pack_jit``, ``_save_pack_sums_jit``) per operation (ms), from the
profiler's trace."""

from layers import save_events


def read(run):
    s = save_events.pack_seconds_per_operation(run)
    return None if s is None else s * 1000.0
