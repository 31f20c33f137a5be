"""Store read-back + host staging: how long an operation's jobs stood
queued for the one landing thread (``sink_wait``: one event a job, stamped
as the job starts on the thread, ``aux`` = the ms since it was submitted
on the event loop), summed per operation, median (ms). In a re-land the
whole landing is one job, so with several clients it holds the other
clients' whole landings; with one client it is near 0 in a re-land and,
in a cold pull, each piece's wait behind its predecessors. A program
older than the event stamps none, and this reads nothing."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_wait")
