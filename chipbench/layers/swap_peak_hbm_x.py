"""Hot-swap: peak bytes in use on the chip, as the runtime counts them over
the whole run, over ONE version's content bytes (x): the live generation's
words and tensors, the new one's beside them, the staging slabs."""

from layers import swap_events


def read(run):
    if not run.memory_peak_bytes or not run.ops:
        return None
    return run.memory_peak_bytes / swap_events.content_bytes(run)
