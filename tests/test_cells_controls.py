"""Every cell's check can fail: whole runs with the landing broken underneath.

A case is one run of a control script of ``chipbench/tests/`` at a cell's
tiny twin (``test_cells_rehearsal.py`` has the sound runs): the script
alters what lands where the program's own verification cannot see it, runs
the whole cell, and the last line's ``correct`` must be false, with no
operation failed: only the benchmark's comparison with the generator
objected. One break is the program's own to catch (the hot-swap's gate over
a corrupt live generation): that run ends with the one swap failed.
"""

import json

import pytest

from test_cells_rehearsal import last_line, whole_run

CONTROLS = [
    # One client, and three clients at the one landing thread.
    ("control.py", ("--break", "flip"), "tiny-shard-reland"),
    ("control.py", ("--break", "zero"), "tiny-shard-reland"),
    ("control.py", ("--break", "flip"), "tiny-tar-reland"),
    ("control.py", ("--break", "zero"), "tiny-tar-reland"),
    ("control_chip.py", ("--chip", "2"), "tiny-shard-reland-4chip"),
    ("control_ranged.py", ("--break", "flip"), "tiny-rank-cold"),
    ("control_ranged.py", ("--break", "stray"), "tiny-rank-cold"),
    ("control_fanout.py", ("--break", "flip"), "tiny-shard-cold-fanout"),
    ("control_fanout.py", ("--break", "source"), "tiny-shard-cold-fanout"),
    ("control_global.py", ("--break", "flip"), "tiny-host-reland-ep4"),
    ("control_global.py", ("--break", "copy"), "tiny-host-reland-ep4"),
    ("control_global.py", ("--break", "misplace"), "tiny-host-reland-ep4"),
    ("control_feed.py", ("--break", "flip"), "tiny-feed-records"),
    ("control_feed.py", ("--break", "swap"), "tiny-feed-records"),
    ("control_feed.py", ("--break", "numpy"), "tiny-feed-records"),
    # The hot-swap: a base chunk corrupt on disk is fetched again and
    # counted (the reference never has it); the wrong version served; a
    # torn snapshot among the reader's notes.
    ("control_swap.py", ("--break", "store"), "tiny-shard-swap"),
    ("control_swap.py", ("--break", "version"), "tiny-shard-swap"),
    ("control_swap.py", ("--break", "torn"), "tiny-shard-swap"),
    # The save and resume: a bit of the replica's stored file differs when
    # the benchmark hashes it.
    ("control_save.py", ("--break", "flip"), "tiny-ckpt-save-resume"),
]


@pytest.mark.parametrize("script, how, cell", CONTROLS, ids=[
    f"{script[:-3]}-{how[1]}-{cell}" for script, how, cell in CONTROLS])
def test_a_broken_landing_comes_out_incorrect(script, how, cell):
    line = last_line(whole_run("tests/" + script, cell, *how), cell)
    assert line["correct"] is False, line


def test_a_corrupt_live_generation_is_refused_by_the_swaps_own_gate():
    """The one break that the program itself must catch: a bit of the live
    generation's words differs inside a run the swap copies HBM -> HBM. The
    gate refuses the flip, so the operation fails, the old generation stays
    live, and the run comes out incorrect with that one failure."""
    proc = whole_run("tests/control_swap.py", "tiny-shard-swap",
                     "--break", "live")
    line = last_line(proc, "tiny-shard-swap", failed=1)
    assert line["correct"] is False, line
    assert "hot-swap verify failed: piece 0 corrupt" in proc.stdout


def test_a_resume_with_no_holder_left_fails_and_is_counted():
    """The second host's copy deleted with the saver's, before the resume:
    the P2P-only pull has no source to go back to, so every operation of the
    window fails with the scheduler's word for it and is counted."""
    proc = whole_run("tests/control_save.py", "tiny-ckpt-save-resume",
                     "--break", "replica")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0 and line["failed"] >= 1, proc.stderr[-2000:]
    assert line["correct"] is False, line
    assert "host 1's copy deleted too" in proc.stdout
    assert "back-to-source" not in proc.stdout
