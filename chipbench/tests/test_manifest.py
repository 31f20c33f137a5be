"""Every name in the manifests leads to a file: the harness finds a cell's
configuration, traffic, driver, generator and per-layer readers by name, so
a later PR adds a cell by adding files and entries and edits nothing."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]


@pytest.mark.parametrize("manifest", ["BENCHMARK.json",
                                      "chipbench/rehearsal/manifest.json",
                                      "chipbench/later/manifest.json"])
def test_names_resolve(manifest):
    with open(os.path.join(REPO, manifest)) as f:
        m = json.load(f)
    if manifest != "BENCHMARK.json":
        # Its own configurations and cells (and, for cells kept for later,
        # their end-to-end metrics); every other table is BENCHMARK.json's,
        # as run.py reads it.
        assert {"configs", "workloads"} <= set(m) <= {
            "rehearsal", "configs", "workloads", "end_to_end"}
        return check_cells(m)
    check_cells(m)
    cells = {w["name"] for w in m["workloads"]}
    ends = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in ends
    for metric in m["per_layer"]:
        assert hasattr(importlib.import_module("layers." + metric["name"]),
                       "read")
        assert metric["moves"] in ends
        assert set(metric.get("workloads", [])) <= cells


def check_cells(m):
    configs = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        with open(os.path.join(REPO, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        importlib.import_module("drivers." + traffic["kind"])
        importlib.import_module("objects." + config["object"]["kind"])


def test_peaks_name_their_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        for kind, row in json.load(f).items():
            assert row["hbm_bytes_per_s"] > 0 and row["source"], kind
