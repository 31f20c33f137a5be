"""Hot-swap: the gate's device program against the memory roofline (%): the
least time the chip could take to checksum the new words, over the device
time of ``_words_checksums_jit`` per operation. Memory-bound: a sum and an
xor a word."""

from layers import swap_events


def least_bytes(content_bytes: float) -> float:
    """Every content byte read once; 8 bytes a piece written."""
    return content_bytes


def read(run):
    took = swap_events.program_seconds_per_operation(
        run, swap_events.VERIFY_PROGRAM)
    if took is None:
        return None
    return 100.0 * least_bytes(swap_events.content_bytes(run)) \
        / run.peaks["hbm_bytes_per_s"] / took
