"""Device: share of the traced operations' time in which no operation ran
on the chip (%), from the profiler's trace."""


def read(run):
    if run.trace is None or not run.window_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
