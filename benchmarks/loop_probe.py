"""What the loop's account costs the loop: a probe of ``pkg/prof.py``
``LoopLagProbe``'s wrapper around ``selector.select``, on one loop with no
daemon, no transfer and no device. It imports no jax.

    chiprun --chips 1 -- python3 benchmarks/loop_probe.py
    python3 benchmarks/loop_probe.py --turns 20000 --repeats 3   # the rehearsal

Two kinds of turn, each timed with the probe armed and not, by turns, the
median and the range over ``--repeats`` passes after one that is not
counted, in microseconds a loop iteration:

  empty turn     one task that does ``await asyncio.sleep(0)``: an iteration
                 is one handle and one zero-timeout ``select``; the account's
                 whole cost shows
  32 handles     32 such tasks sharing the turns: an iteration runs 32
                 handles, so the account's cost a handle is a 32nd

With the probe armed a recorder is attached, as in a daemon, so the slices
(one every 5 ms of busy time) are stamped on the ring ``runtime:loop:probe``;
the last column is the slices a second that the armed passes stamped. The
budget is 2 us an iteration (PERF.md, layer "event loop"). The table goes to
stdout and to ``chiprun_out/loop_probe.json``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


async def _spin(turns: int) -> None:
    for _ in range(turns):
        await asyncio.sleep(0)


async def _turns(tasks: int, turns: int) -> float:
    """Seconds a loop iteration, ``tasks`` handles a turn."""
    t0 = time.perf_counter()
    await asyncio.gather(*(_spin(turns) for _ in range(tasks)))
    return (time.perf_counter() - t0) / turns


async def _measure(tasks: int, turns: int, repeats: int) -> dict:
    from dragonfly2_tpu.pkg import flight, prof

    obs = prof.RuntimeObservatory(prof.ProfConfig(),
                                  recorder=flight.FlightRecorder())
    ring = obs.loop_ring("probe")
    off, on, rate = [], [], 0.0
    for i in range(repeats + 1):
        plain = await _turns(tasks, turns)
        probe = obs.arm_loop("probe")
        before, t0 = ring.events_total, time.perf_counter()
        armed = await _turns(tasks, turns)
        rate = (ring.events_total - before) / (time.perf_counter() - t0)
        probe.disarm()
        if i:                   # the first pass warms both up
            off.append(plain * 1e6)
            on.append(armed * 1e6)
    return {"handles": tasks, "turns": turns,
            "off_us": statistics.median(off), "off_range": [min(off), max(off)],
            "on_us": statistics.median(on), "on_range": [min(on), max(on)],
            "cost_us": statistics.median(on) - statistics.median(off),
            "slices_per_s": rate}


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--turns", type=int, default=200000)
    parser.add_argument("--repeats", type=int, default=7)
    args = parser.parse_args(argv)
    rows = [asyncio.run(_measure(tasks, args.turns // tasks, args.repeats))
            for tasks in (1, 32)]
    print(f"{'turn':<12} {'not armed us':>22} {'armed us':>22} "
          f"{'cost us':>8} {'a handle':>9} {'slices/s':>9}")
    for row in rows:
        name = "empty turn" if row["handles"] == 1 else "32 handles"
        print(f"{name:<12} "
              f"{row['off_us']:8.3f} ({row['off_range'][0]:.3f}-"
              f"{row['off_range'][1]:.3f}) "
              f"{row['on_us']:8.3f} ({row['on_range'][0]:.3f}-"
              f"{row['on_range'][1]:.3f}) "
              f"{row['cost_us']:8.3f} {row['cost_us'] / row['handles']:9.3f} "
              f"{row['slices_per_s']:9.1f}")
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "loop_probe.json"), "w") as f:
        json.dump({"python": sys.version.split()[0], "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
