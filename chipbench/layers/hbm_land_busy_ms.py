"""Store read-back + host staging: the union of the hbm_start ->
hbm_landed intervals of an operation's pieces, median per operation (ms).
Each interval is stamped around an await on the one landing thread, so it
holds a piece's queue wait too; the union is the time that thread had
work. A re-land stamps nothing, and then this reads nothing."""

import spans
import reduce_trace as trace


def read(run):
    per_op = [trace.total(spans.paired(op, "hbm_start", "hbm_landed"))
              for op in run.ops]
    return spans.median_ms(t for t in per_op if t > 0)
