"""Checkpoint save / resume: the share of a save's pieces that the saver's
upload side had already served to the replica's host when ``Finished`` was
sent (%): ``save_replica_ahead``'s ``piece`` (one point a save, stamped as
``Finished`` is sent) over the operation's piece count (``save_snapshot``'s
``piece``). 0 is a replica pulled after the import, near 100 one pulled
beside it, of which ``save_replicate_ms`` then holds only the tail. Median
per operation. A program that does not stamp the point reads nothing."""

from layers import save_events


def ahead_pct(op):
    served = [piece for _, event, piece, _ in op.flight
              if event == "save_replica_ahead"]
    pieces = [piece for _, event, piece, _ in op.flight
              if event == "save_snapshot"]
    if not served or not pieces or pieces[0] <= 0:
        return None
    return 100.0 * served[0] / pieces[0]


def read(run):
    return save_events.median(ahead_pct(op) for op in run.ops)
