"""Piece transfer: the origin's part of the wait for a task's first piece,
as the seed measured it: ``parent_source_first_byte`` is the seed's
``source_first_byte`` (an origin request issued -> its first body byte; the
first piece's arrival where the seed's native client hands back whole
pieces), carried to the peer with the announcement of the piece. Per
operation the sum over its tasks (one; the ranged tasks of ``op.ranged``
where the operation is a sharded pull) of the task's EARLIEST such event's
``aux``; median over the operations (ms). ``seed_start_ms`` less this is
what the program itself spends before the first announcement."""

from layers import ranged_events


def of_operation(op) -> float | None:
    firsts = [next((aux for _, event, _, aux in flight
                    if event == "parent_source_first_byte"), None)
              for flight in ranged_events.tasks(op) or [op.flight]]
    found = [aux for aux in firsts if aux is not None]
    return sum(found) if found else None


def read(run):
    return ranged_events.median_per_operation(run, of_operation)
