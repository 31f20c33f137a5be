"""Every cell's check can fail: whole runs with the landing broken underneath.

A case is one run of a control script of ``chipbench/tests/`` at a cell's
tiny twin (``test_cells_rehearsal.py`` has the sound runs): the script
alters what lands where the program's own verification cannot see it, runs
the whole cell, and the last line's ``correct`` must be false, with no
operation failed: only the benchmark's comparison with the generator
objected.
"""

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
]


@pytest.mark.parametrize("script, how, cell", CONTROLS, ids=[
    f"{script[:-3]}-{how[1]}-{cell}" for script, how, cell in CONTROLS])
def test_a_broken_landing_comes_out_incorrect(script, how, cell):
    line = last_line(whole_run("tests/" + script, cell, *how), cell)
    assert line["correct"] is False, line
