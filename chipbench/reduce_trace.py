"""From a profiler trace to numbers: the reduction every PR shares.

``read_xplane`` turns the ``.xplane.pb`` jax's profiler writes into a small
plain dictionary (the recorded trace beside the tests has this form):

    {"device": {plane name: {line name: [[event name, start_s, dur_s]]}},
     "host": [[annotation name, start_s, dur_s]]}

Only the benchmark's own ``chipbench:`` annotations are kept from the host
planes. Everything below that works on this dictionary and on plain
intervals, so it is checked without a chip (tests/test_trace.py).
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINES = ("XLA Ops",)            # one event per device operation
MODULE_LINES = ("XLA Modules",)     # one event per program run
MARK = "chipbench:"
OWN = "chipbench_"                  # the benchmark's own device programs


def read_xplane(trace_dir: str) -> dict:
    """The newest trace under ``trace_dir`` (what jax.profiler wrote)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = out["device"].setdefault(plane.name, {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    [ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9]
                    for ev in line.events)
        else:
            for line in plane.lines:
                out["host"].extend(
                    [ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9]
                    for ev in line.events if ev.name.startswith(MARK))
    return out


def chip_planes(trace: dict) -> list[str]:
    """Device planes of chips (``/device:TPU:0``), not their sub-units."""
    names = [n for n in trace["device"]
             if n.startswith("/device:TPU:") and n.split(":")[-1].isdigit()]
    return sorted(names)


def events_on(trace: dict, plane: str, lines: tuple[str, ...]) -> list:
    found = []
    for name in lines:
        found += trace["device"].get(plane, {}).get(name, [])
    return found


def program_ops(trace: dict, plane: str) -> list:
    """The device operations of the program under test: those that began
    inside a run of one of the benchmark's own programs (its checksums of
    what landed, which with several clients fall inside other clients'
    operations) are left out."""
    own = union((s, s + d) for name, s, d in
                events_on(trace, plane, MODULE_LINES) if OWN in name)
    starts = [s for s, _ in own]
    kept = []
    for ev in events_on(trace, plane, OPS_LINES):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0 or ev[1] >= own[i][1]:
            kept.append(ev)
    return kept


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, windows) -> list[tuple[float, float]]:
    """The parts of ``intervals`` inside the union of ``windows``."""
    out = []
    for ws, we in union(windows):
        for s, e in intervals:
            s, e = max(s, ws), min(e, we)
            if e > s:
                out.append((s, e))
    return out


def clock_offset(trace: dict, marks: dict[str, float]) -> float:
    """Seconds to add to a perf_counter reading to get the trace's clock:
    the median, over the benchmark's annotations, of where the trace saw
    one begin less where this process noted entering it."""
    deltas = sorted(start - marks[name] for name, start, _ in trace["host"]
                    if name in marks)
    if not deltas:
        raise ValueError("none of the benchmark's annotations is in the "
                         "trace: the clocks cannot be aligned")
    return deltas[len(deltas) // 2]


def busy_and_window(trace: dict, windows) -> tuple[float, float]:
    """(busy_s, window_s): seconds in which an operation ran on the
    device inside the traced operations, averaged over the chips, and the
    length of the union of those operations."""
    planes = chip_planes(trace)
    if not planes:
        raise ValueError("the trace holds no chip's plane")
    busy = 0.0
    for plane in planes:
        ops = [(s, s + d) for _, s, d in program_ops(trace, plane)]
        busy += total(clip(ops, windows))
    return busy / len(planes), total(windows)


def program_seconds(trace: dict, needles: tuple[str, ...], windows) -> float:
    """Summed device seconds of the runs of every program whose name
    holds one of ``needles``, inside ``windows``, on the first chip."""
    planes = chip_planes(trace)
    if not planes:
        return 0.0
    runs = [(s, s + d) for name, s, d in
            events_on(trace, planes[0], MODULE_LINES)
            if any(n in name for n in needles)]
    return sum(e - s for s, e in clip(runs, windows))


def top_device_ops(trace: dict, windows, n: int = 10) -> list:
    """[[operation name, seconds]]: where the device's busy time went."""
    planes = chip_planes(trace)
    by_name: dict[str, float] = {}
    for plane in planes:
        for name, s, d in program_ops(trace, plane):
            for cs, ce in clip([(s, s + d)], windows):
                by_name[name] = by_name.get(name, 0.0) + (ce - cs)
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:120], seconds / max(1, len(planes))]
            for name, seconds in rows]


def idle_gaps_by_label(trace: dict, windows, labelled, n: int = 10) -> list:
    """[[what the host was doing, idle seconds]]: each gap between device
    operations inside ``windows`` goes, piece by piece, to the labelled
    host interval that covers it (the first label listed wins where
    several do), the rest to ``unlabelled``. ``labelled`` is
    [(label, start, end)] on the trace's clock."""
    planes = chip_planes(trace)
    if not planes:
        return []
    ops = union((s, s + d) for _, s, d in program_ops(trace, planes[0]))
    gaps = []
    for ws, we in union(windows):
        at = ws
        for s, e in ops:
            if e <= ws or s >= we:
                continue
            if s > at:
                gaps.append((at, min(s, we)))
            at = max(at, e)
        if at < we:
            gaps.append((at, we))
    by_label: dict[str, float] = {}
    for gs, ge in gaps:
        covered: list[tuple[float, float]] = []
        for label, ls, le in labelled:
            part = clip([(max(gs, ls), min(ge, le))], [(gs, ge)])
            fresh = total(part) - total(clip(part, covered)) if covered \
                else total(part)
            if fresh > 0:
                by_label[label] = by_label.get(label, 0.0) + fresh
                covered = union(covered + part)
        rest = (ge - gs) - total(covered)
        if rest > 0:
            by_label["unlabelled"] = by_label.get("unlabelled", 0.0) + rest
    rows = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[label, seconds] for label, seconds in rows]
