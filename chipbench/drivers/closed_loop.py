"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
operation when the last one has finished. No rate to search for: a slower
system is offered less.

Parameters of a traffic file of this kind:
  clients   callers running side by side
  mode      "cold": every operation a new task, pulled through the whole
            fabric and deleted from both stores afterwards;
            "reland": cold pulls in set-up leave the object in the
            peer's store, and every operation lands it again from there
  objects   "reland" over distinct objects only: how many of them set-up
            leaves in the store, a multiple of ``clients``; each client
            goes round its own share of them
  trace     how much of the window a traced run covers:
            {"operations": n} or {"seconds": s}
"""

from __future__ import annotations

import asyncio
import itertools
import time


async def warm_up(cell) -> None:
    """One untimed operation of the cell's own geometry (two where the
    first has to fill the store), so nothing the window runs is new."""
    # The seed's socket is up before the scheduler has its announce, so a
    # first pull may be sent back to the source: set-up's race, not the
    # cell's path. Such a warm-up is marked and, where it can be, repeated.
    if cell.mode == "reland":
        clients = int(cell.traffic["clients"])
        numbers = itertools.count(-2, -1)
        todo = list(range(cell.stored if cell.objects.distinct else 1))

        async def fill(c: int) -> None:
            while todo:
                op = await cell.operation(next(numbers), c, warmup=True,
                                          cold=True, index=todo.pop(0))
                op.raced = not op.error and not op.from_p2p

        await asyncio.gather(*(fill(c) for c in range(clients)))
        await cell.operation(-1, 0, warmup=True)
        return
    for attempt in range(4):
        op = await cell.operation(-1 - attempt, 0, warmup=True)
        if op.error or op.from_p2p:
            return
        op.raced = True


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """Operations start while the window is open; one that has started
    runs to its end. Returns the window's (start, end) on perf_counter."""
    limit = cell.traffic.get("trace", {}) if traced else {}
    seconds = min(seconds, limit.get("seconds", seconds))
    most = limit.get("operations")
    numbers = itertools.count()
    start = time.perf_counter()

    async def client(c: int) -> None:
        while time.perf_counter() - start < seconds:
            n = next(numbers)
            if most is not None and n >= most:
                return
            await cell.operation(
                n, c, closing=lambda: time.perf_counter() - start >= seconds)

    await asyncio.gather(*(client(c)
                           for c in range(int(cell.traffic["clients"]))))
    return start, time.perf_counter()
