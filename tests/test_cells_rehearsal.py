"""Every cell of the benchmark, rehearsed as a whole run.

``chipbench/rehearsal/`` has a tiny twin of each cell of ``BENCHMARK.json``.
A case here is one run of ``chipbench/run.py`` at that twin in a process of
its own, on the CPU backend: the cell's own driver starts the same children,
pulls through the same fabric and makes the cell's own check, so a change
that breaks a cell's driver, check or fabric shows up here and not on the
chip. The controls, whose ``correct`` must come out false, are
``test_cells_controls.py``: a file of its own, so that the two go to two
workers.
"""

import json
import os
import subprocess
import sys
import time
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")

# cell -> (its rehearsal manifest, the devices it needs)
CELLS = {
    "tiny-shard-cold": ("manifest.json", 1),
    "tiny-shard-reland": ("manifest.json", 1),
    "tiny-tar-cold": ("manifest.json", 1),
    "tiny-tar-reland": ("manifest.json", 1),
    "tiny-shard-reland-4chip": ("manifest-4chip.json", 4),
    "tiny-rank-cold": ("manifest-ranged.json", 1),
    "tiny-shard-cold-fanout": ("manifest-fanout.json", 1),
    "tiny-host-reland-ep4": ("manifest-global.json", 4),
    "tiny-feed-records": ("manifest-feed.json", 1),
    "tiny-shard-swap": ("manifest-swap.json", 1),
    "tiny-ckpt-save-resume": ("manifest-save.json", 1),
}

# Every process of a run inherits its environment from the run, so a
# variable that names the run finds whatever the run left behind, whichever
# scratch home ``chipbench/fabric.py`` chose for it.
RUN_TAG = "CELLS_REHEARSAL_RUN"


def _processes_of(run: str) -> list[str]:
    wanted = f"{RUN_TAG}={run}".encode()
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if wanted not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        found.append(f"{pid}: {cmdline[:200]}")
    return found


def whole_run(script: str, cell: str, *extra: str):
    """One run of ``chipbench/<script>`` at ``cell`` (a tiny cell under its
    rehearsal manifest, any other under ``BENCHMARK.json``), to its end; the
    finished process, once none of the processes it started is left."""
    # A whole run is four processes and some forty compiles. At the lowest
    # priority, on one compute thread, so that the tests of the other
    # workers that assert on latency under load keep the cores (three runs
    # of the whole suite with such runs at full priority failed one of them
    # each, three without them none).
    manifest, chips = CELLS.get(cell, (None, 1))
    run = uuid.uuid4().hex
    argv = ["nice", "-n", "19", sys.executable, os.path.join(BENCH, script),
            *extra, "--workload", cell, "--seed", "2147484034",
            "--seconds", "1", "--trace", "0"]
    if manifest:
        argv += ["--manifest", os.path.join(BENCH, "rehearsal", manifest)]
    proc = subprocess.run(
        argv, env=dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1",
                       XLA_FLAGS=(
                           f"--xla_force_host_platform_device_count={chips} "
                           "--xla_cpu_multi_thread_eigen=false "
                           "intra_op_parallelism_threads=1"),
                       **{RUN_TAG: run}),
        capture_output=True, text=True, timeout=600, cwd=REPO)
    deadline = time.monotonic() + 10
    while (left := _processes_of(run)) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert left == []
    return proc


def last_line(proc, cell: str, failed: int = 0) -> dict:
    """What every whole run of a tiny cell ends with, sound or broken: exit
    0 and the JSON as the last line, no operation failed (but where the
    break is one that the program itself must refuse), nothing of a CPU
    run under a device metric's name."""
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == failed and line["attempted"] >= 1, line
    assert line["metrics"] == {} and line["rehearsal"] is True, line
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": CELLS[cell][1],
                              "memory_peak_bytes": None}, line
    return line


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_rehearsal_is_correct_and_leaves_no_process(cell):
    line = last_line(whole_run("run.py", cell), cell)
    assert line["correct"] is True, line


def test_a_real_cell_refuses_to_run_off_the_chip():
    """No rehearsal manifest, so ``BENCHMARK.json``'s own cell: it prints
    no result anywhere but on a TPU, and the children it started while jax
    came up are gone when it returns."""
    proc = whole_run("run.py", "shard-reland")
    assert proc.returncode == 1 and proc.stdout == "", proc.stdout[-800:]
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
