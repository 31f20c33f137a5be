"""Host intervals of one operation, from the peer's raw flight events.

``op.flight`` is [(perf_counter seconds, event name, piece, aux)]: the
events the program's flight recorder kept for the operation's task, by the
names the program gives them. The program's own fold (``flight.analyze``)
books HBM landing under ``ici`` and hides overlapped work, so the
benchmark pairs the raw events itself.
"""

from __future__ import annotations

import statistics


def paired(op, begin: str, end: str) -> list[tuple[float, float]]:
    """(start, end) for each piece's ``begin`` event and the next ``end``
    event of the same piece."""
    open_at: dict[int, float] = {}
    out = []
    for t, name, piece, _ in op.flight:
        if name == begin:
            open_at[piece] = t
        elif name == end and piece in open_at:
            out.append((open_at.pop(piece), t))
    return out


def transfers(op) -> list[tuple[float, float]]:
    """Piece request -> landed; a piece landed with no request on record
    (the native span path) is backed out from its cost in ms."""
    out = paired(op, "request", "landed")
    requested = {piece for _, name, piece, _ in op.flight
                 if name == "request"}
    out += [(t - aux / 1000.0, t) for t, name, piece, aux in op.flight
            if name == "landed" and piece not in requested and aux > 0]
    return out


def sched_wait(op) -> list[tuple[float, float]]:
    """Register -> the scheduler's first answer."""
    start = next((t for t, name, _, _ in op.flight if name == "register"),
                 None)
    if start is None:
        return []
    end = next((t for t, name, _, _ in op.flight
                if name == "scheduled" and t >= start), None)
    return [] if end is None else [(start, end)]


def labelled(op) -> list[tuple[str, float, float]]:
    """What the host was doing during the operation, most specific first:
    for naming the device's idle gaps."""
    rows = [("views: load_safetensors", *op.views_span)] \
        if op.views_span else []
    rows += [("hbm landing: read-back, staging, device_put", s, e)
             for s, e in paired(op, "hbm_start", "hbm_landed")]
    rows += [("sha256 of the whole object", s, e)
             for s, e in paired(op, "verify_start", "verified")]
    rows += [("piece transfer", s, e) for s, e in transfers(op)]
    rows += [("scheduler wait", s, e) for s, e in sched_wait(op)]
    times = {name: [t for t, n, _, _ in op.flight if n == name]
             for name in ("scheduled", "request", "hbm_landed")}
    if times["scheduled"] and times["request"]:
        rows.append(("wait for the first piece (the seed's back-to-source "
                     "start)", times["scheduled"][0], times["request"][0]))
    if times["hbm_landed"]:
        rows.append(("finalize: flush, assemble (and its compile), verify "
                     "on device", times["hbm_landed"][-1],
                     op.views_span[0] if op.views_span else op.t1))
    rows.append(("operation, no flight event (a re-land: backfill from the "
                 "store, assemble, verify)", op.t0, op.t1))
    return rows


def median_ms(values) -> float | None:
    values = list(values)
    return statistics.median(values) * 1000.0 if values else None


def timeline(op) -> str:
    """One line for a person: when each kind of event first and last fell,
    in seconds after the request, and the order in which pieces reached
    the landing thread (which fixes the assembly program's plan)."""
    first: dict[str, float] = {}
    last: dict[str, float] = {}
    for t, name, _, _ in op.flight:
        first.setdefault(name, t - op.t0)
        last[name] = t - op.t0
    parts = [f"{name} {first[name]:.2f}" + (
        f"..{last[name]:.2f}" if last[name] - first[name] > 0.005 else "")
        for name in first]
    if op.views_span:
        parts.append(f"views {op.views_span[0] - op.t0:.2f}")
    parts.append(f"ready {op.t1 - op.t0:.2f}")
    order = [piece for _, name, piece, _ in op.flight if name == "hbm_start"]
    return ", ".join(parts) + f"; landing order {order}"
