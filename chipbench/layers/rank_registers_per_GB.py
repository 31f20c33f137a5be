"""Ranged pull: ``register`` events of an operation (one a ranged task, the
header's too, and one more for every task the scheduler sent round again)
over its resident bytes in GB (1e9): ROADMAP R1's registers per GiB, what
coalescing keeps down. Median per operation."""

from layers import ranged_events


def of_operation(op) -> float | None:
    registers = sum(1 for flight in ranged_events.tasks(op)
                    for _, name, _, _ in flight if name == "register")
    return registers / (op.nbytes / 1e9) if registers and op.nbytes else None


def read(run):
    return ranged_events.median_per_operation(run, of_operation)
