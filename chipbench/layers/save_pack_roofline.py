"""Checkpoint save / resume: the pack's device programs against the memory
roofline (%): the least time the chip could take to build and checksum the
file's words, over the device time of ``_save_pack_jit`` and
``_save_pack_sums_jit`` per operation. Memory-bound: a move and a sum and
an xor a word."""

from layers import save_events


def least_bytes(content_bytes: float) -> float:
    """Every tensor byte read once and written once as a word of the file,
    every word read once for the checksum: three passes over the content
    (the header's few KB and the zeros behind the last piece's content are
    left out, which only lowers the share)."""
    return 3.0 * content_bytes


def read(run):
    took = save_events.pack_seconds_per_operation(run)
    if took is None:
        return None
    return 100.0 * least_bytes(save_events.content_bytes(run)) \
        / run.peaks["hbm_bytes_per_s"] / took
