"""Sharded landing: the fullest chip's peak bytes in use, as the runtime
counts them over the whole run, over the bytes resident on that chip at an
operation's end (x): the largest of the chips' ratios. ``peak_hbm_x`` reads
chip 0 against the file; this reads every chip against its own share."""


def read(run):
    peaks = getattr(run.cell, "chip_peaks", None)
    shares = [op.chip_resident for op in run.ops
              if getattr(op, "chip_resident", None)]
    if not peaks or not shares or len(peaks) != len(shares[-1]):
        return None
    ratios = [peak / held for peak, held in zip(peaks, shares[-1])
              if peak and held]
    return max(ratios) if ratios else None
