"""``correct`` comes out false when the landing is broken underneath, and
true when it is not: whole runs of run.py at the rehearsal's size on the
CPU backend (the manifest's ``rehearsal`` flag is what lets a run go on
without a chip; everything after that look is the code a chip run uses).

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import control  # noqa: E402
import run  # noqa: E402

MANIFEST = os.path.join(os.path.dirname(HERE), "rehearsal", "manifest.json")


def last_line(capsys, workload: str, how: str | None, seed: int) -> dict:
    argv = ["--manifest", MANIFEST, "--workload", workload, "--seed",
            str(seed), "--seconds", "1", "--trace", "0"]
    if how is None:
        assert run.main(argv) == 0
    else:
        with control.broken(how):
            assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["tiny-shard-cold", "tiny-tar-cold",
                                      "tiny-shard-reland", "tiny-tar-reland"])
@pytest.mark.parametrize("how", [None, "flip", "zero"])
def test_correct_follows_the_landing(capsys, workload, how):
    line = last_line(capsys, workload, how, seed=2147484001)
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["correct"] is (how is None), line
    # A CPU run reports no number under a device metric's name.
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"


def test_a_real_cell_refuses_to_run_off_the_chip(capsys):
    rc = run.main(["--workload", "shard-cold", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "needs a TPU" in out.err
