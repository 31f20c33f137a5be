"""The records a reader meets before the ledger, held to the record that is
read.

Speed is measured by one benchmark (``BENCHMARK.json`` + ``chipbench/``) and
recorded in ``PERF_LEDGER.jsonl`` / ``PERF.md``. Tier-1 runs ``tests/`` only,
so this file reads those files as text and JSON (it imports nothing from
``chipbench/``) and checks that every name the manifest declares leads
somewhere, that ``BASELINE.json`` stays the specification it was cut to, and
that the prose documents state no rate without saying where it was measured.
"""

from __future__ import annotations

import functools
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def _text(path: str) -> str:
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return f.read()


MANIFEST = json.loads(_text("BENCHMARK.json"))
CELLS = {w["name"] for w in MANIFEST["workloads"]}
ENDS = {m["name"] for m in MANIFEST["end_to_end"]}
ENTRIES = [(kind, entry) for kind in ("workloads", "end_to_end", "per_layer")
           for entry in MANIFEST[kind]]


@pytest.mark.parametrize(
    "kind,entry", ENTRIES,
    ids=[f"{kind}:{entry['name']}" for kind, entry in ENTRIES])
def test_every_name_of_the_benchmark_leads_somewhere(kind, entry):
    name = entry["name"]
    assert f"`{name}`" in _text("PERF.md"), f"PERF.md never names {name}"
    if kind == "workloads":
        config = next(c for c in MANIFEST["configs"]
                      if c["name"] == entry["config"])
        on_file = json.loads(_text(config["file"]))
        assert on_file["name"] == config["name"]
        assert on_file["source"] and on_file["source"] == config["source"]
        assert _text(f"chipbench/traffic/{entry['traffic']}.json")
    elif kind == "per_layer":
        reader = _text(f"chipbench/layers/{name}.py")
        assert re.search(r"^def read\(run\)", reader, re.M), name
        assert entry["moves"] in ENDS
        assert set(entry.get("workloads", [])) <= CELLS


def test_baseline_json_is_the_specification_and_holds_no_result():
    spec = json.loads(_text("BASELINE.json"))
    assert set(spec) == {"metric", "reference_repo", "reference_path",
                         "north_star", "configs"}
    assert len(spec["configs"]) == 5 and all(
        isinstance(c, str) and c for c in spec["configs"])


# A rate as prose states one: "3,034.7 MB/s", "819 GB/s", "65 MiB/s".
RATE = re.compile(r"\d ?(GB|MB|GiB|MiB)/s")
# Where it was measured, in PERF.md's own tags, or a pointer at PERF.md.
ORIGIN = re.compile(r"ledger, PR \d+|my chip runs?, PRs? \d+|PERF\.md")


@pytest.mark.parametrize("path", ["README.md", "docs/ARCHITECTURE.md",
                                  "docs/ZERO_COPY.md",
                                  "docs/OBSERVABILITY.md"])
def test_a_stated_rate_names_its_origin(path):
    # A paragraph, a list item or a table row, not a line: where prose is
    # wrapped is chance.
    for block in re.split(r"\n\s*\n|\n(?=\s*(?:[-*] |\d+\. |\|))",
                          _text(path)):
        if RATE.search(block):
            assert ORIGIN.search(block), (
                f"{path} states a rate without its origin (\"ledger, PR n\", "
                f"\"my chip runs, PR n\" or a pointer at PERF.md):\n"
                f"{block[:400]}")


# What a document names in backticks and that looks like a file of this
# repository: a path or a bare name that ends in one of these, with an
# optional ``:line`` or ``:line-line`` behind it. A placeholder (``<name>``,
# ``*``) does not match.
NAMED_FILE = re.compile(
    r"`((?:[\w.-]+/)*[\w.-]+\.(?:py|json|md|sh))(?::\d+(?:-\d+)?)?`")
TOP_LEVEL = ("dragonfly2_tpu", "tests", "benchmarks", "chipbench", "docs",
             "examples", "deploy", ".claude")
# History, not a pointer to follow: a line that says where something was
# "copied from" (the benchmark's own README says so of its fabric, PR 22, and
# only a ``benchmark`` PR may reword it: ROADMAP D16).
HISTORY = "copied from"


@functools.cache
def _file_names() -> frozenset:
    names = set()
    for top in TOP_LEVEL:
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    names.update(f for f in os.listdir(REPO)
                 if os.path.isfile(os.path.join(REPO, f)))
    return frozenset(names)


@pytest.mark.parametrize("path", ["README.md", "docs/ARCHITECTURE.md",
                                  "docs/ZERO_COPY.md",
                                  "docs/OBSERVABILITY.md",
                                  "chipbench/README.md",
                                  ".claude/skills/verify/SKILL.md"])
def test_a_named_file_exists(path):
    """A path is read from the root, from the package (``ops/hbm_sink.py``)
    or from the document's own directory; a bare name is any file of the
    tree. A path whose first directory is none of ours is not ours to
    check."""
    roots = (REPO, os.path.join(REPO, "dragonfly2_tpu"),
             os.path.join(REPO, os.path.dirname(path)))
    stale = set()
    pointers = "\n".join(line for line in _text(path).splitlines()
                         if HISTORY not in line)
    for named in NAMED_FILE.findall(pointers):
        first, slash, _ = named.partition("/")
        if not slash:
            if named not in _file_names():
                stale.add(named)
        elif any(os.path.isdir(os.path.join(root, first)) for root in roots):
            if not any(os.path.isfile(os.path.join(root, named))
                       for root in roots):
                stale.add(named)
    assert not stale, f"{path} names files that do not exist: {sorted(stale)}"
