"""The controls of the cell whose operation is eight hosts pulling at once:
one host's result is altered underneath the program, which ``correct`` has
to catch.

    python3 chipbench/tests/control_fanout.py --break flip --workload shard-cold-fanout --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place and prints the usual last line,
whose ``correct`` must be false. Both breaks leave host 0, the chip and
every daemon's own verification sound, so only the benchmark's readings of
the OTHER hosts can object:

  flip    one bit of one byte of host 3's output file (the store's own file,
          hard-linked) differs once the pull is over, before the benchmark
          sums it: the warm-up and the window's last operation sum every
          host's file
  source  host 5 runs with no scheduler, so it goes back to the source for
          every task: ``from_p2p`` false on that host, and the origin serves
          the content twice an operation
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    import fabric
    from drivers import closed_loop_fanout as driver

    sound_check, sound_spawn = driver.check_hosts, fabric.Fabric.spawn

    async def check_hosts(cell, op, finals, every_file):
        path = cell.hosts[2].output(op.tag)       # host 3
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 3)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0x10]))
        return await sound_check(cell, op, finals, every_file)

    def spawn(self, name, argv):
        if name == "h5":
            at = argv.index("--scheduler")
            argv = argv[:at] + argv[at + 2:]
        return sound_spawn(self, name, argv)

    if how == "flip":
        driver.check_hosts = check_hosts
    elif how == "source":
        fabric.Fabric.spawn = spawn
    else:
        raise SystemExit(f"control_fanout: no break named {how!r}")
    try:
        yield
    finally:
        driver.check_hosts, fabric.Fabric.spawn = sound_check, sound_spawn


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
