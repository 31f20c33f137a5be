"""Completion digest: how much of the object the seed's hasher had still to
hash when the seed's last piece had landed: ``parent_verified``'s piece
(pieces still to hash at ``verify_start``) over the object's pieces, x 100,
median per operation. 0 is a perfect overlap of the whole-object digest with
the transfer; 100 a digest that starts when the transfer ends."""

from layers import ranged_events


def behind_pct(op) -> float | None:
    behind = [piece for _, event, piece, _ in op.flight
              if event == "parent_verified" and piece >= 0]
    if not behind or op.nbytes <= 0 or op.piece_bytes <= 0:
        return None
    pieces = -(-op.nbytes // op.piece_bytes)
    return 100.0 * max(behind) / pieces


def read(run):
    return ranged_events.median_per_operation(run, behind_pct)
