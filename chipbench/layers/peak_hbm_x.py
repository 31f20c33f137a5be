"""Device: peak bytes in use on the chip, as the runtime counts them, over
one object's content bytes."""


def read(run):
    if not run.memory_peak_bytes or not run.ops:
        return None
    content = sum(op.nbytes for op in run.ops) / len(run.ops)
    return run.memory_peak_bytes / content
