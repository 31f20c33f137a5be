"""Probe: the fan-out cell's operation with the chipless hosts asking for the
striped slice broadcast (``pod_broadcast``, what ``dfget --pod-broadcast``
sends), beside the plain path's: ROADMAP S5's reading of the striped
broadcast on the chip's machine. Host 0 cannot ask
(``client.device.download_to_device`` has no such argument) and pulls plainly.

    python3 benchmarks/broadcast_probe.py --workload shard-cold-fanout --seed 7 --seconds 51 --trace 0

runs the whole cell that way and prints the benchmark's usual lines: the
time to the last host (``resident_MBps``), the check's origin line, and the
row a daemon of the last operation (bytes by source, parents). Numbers go
into ``PERF.md``, not into the benchmark. ``--manifest
chipbench/rehearsal/manifest-fanout.json --workload tiny-shard-cold-fanout``
rehearses it on the CPU (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import functools
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "chipbench"), REPO]


def main(argv: list[str]) -> int:
    import run
    from drivers import closed_loop_fanout as driver

    driver.operation = functools.partial(driver.operation, pod_broadcast=True)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
