"""The layer "event loop": the account the peer's loop thread keeps of
itself, shared by the ``loop_*`` readers beside this file (it reads no metric
itself).

``pkg/prof.py`` ``LoopLagProbe`` wraps the running loop's ``selector.select``
and books every iteration (``select`` exit -> the next ``select`` entry) on a
ring of its own, ``runtime:loop:daemon``, which the process's flight recorder
(``dragonfly2_tpu.pkg.flight.recorder()``) holds outside its index of tasks
and which outlives the daemon's stop:

  ``loop_acct``  a SLICE: one event as a turn ends once 5 ms of busy time
                 have gathered since the last (and at every hold's end),
                 ``aux`` = those busy ms, ``piece`` = the thread's own cpu us
                 inside them, ``note`` = ``late=<ms> gc=<ms> it=<iterations>
                 n=<handles>``
  ``loop_lag``   a HOLD: one event as a turn (``note`` ``held n=.. gc=..
                 who=<file:func:line>``) or a late wake (``late``) of 20 ms
                 and more ends, ``aux`` = its SECONDS, ``piece`` = cpu ms

The embedded daemon's loop is the benchmark process's own: the driver's
coroutines share it, as a trainer's share it with the client API. The ring is
on ``perf_counter`` with ``start_wall``, put on the operations' clock as
``harness._read_flight`` puts a task's. An event is a span that ends where it
is stamped: a hold its seconds long, a slice its busy ms (the turns it sums
lie in the 5 ms and more before its end; it is taken as one stretch there, which
it is where one long turn made it). It is CLIPPED to the UNION of the finished
operations, so three clients in flight at once are counted once, and it counts
by the share of its span inside (an operation's last turn ends a moment after
the benchmark's clock read its ``t1``, and counts but for that moment); the
readers give a sum over that union per operation finished. A program older than
the ring has none, and every reader then reads nothing.

What no metric holds goes to the run's log (``describe``, printed once a run by
``loop_hold_ms``): the turns a second, the holds by ``who``, each of the
longest and every one of 0.25 s and more with its operation and its offset
into it, and ``events_dropped`` of the operations' own flights, into which
every hold is stamped too.
"""

import time

from layers.feed_events import fields

RING = "runtime:loop:daemon"


def find_ring():
    from dragonfly2_tpu.pkg import flight as flightlib

    return flightlib.recorder().get(RING)


def inside(run, name: str):
    """``(t, piece, aux, note, share)`` of the ring's ``name`` events whose
    span (``aux``: ms of a slice, seconds of a hold, ending at ``t``) lies
    at least in part inside the union of the run's operations: ``t`` on the
    operations' clock, ``share`` the part of the span inside. None where
    there is no ring or no operation."""
    import reduce_trace
    from dragonfly2_tpu.pkg import flight as flightlib

    ring = find_ring()
    union = reduce_trace.union((op.t0, op.t1) for op in run.ops)
    if ring is None or not union:
        return None
    # The ring's clock is perf_counter since its start; its start as
    # perf_counter follows from its anchored wall start.
    start = time.perf_counter() - (flightlib.anchored_wall()
                                   - ring.start_wall)
    names = flightlib.EVENT_NAMES
    seconds = 0.001 if name == "loop_acct" else 1.0
    out = []
    for t, code, piece, aux, note in ring.events():
        if names.get(code) != name:
            continue
        t += start
        span = aux * seconds
        if span > 0:
            share = reduce_trace.total(
                reduce_trace.clip([(t - span, t)], union)) / span
        else:       # a point: a slice of a late wake and no busy time
            share = float(any(s <= t <= e for s, e in union))
        if share > 0:
            out.append((t, piece, aux, note, share))
    return out


def slices(run):
    """``(busy ms, cpu us, late ms, gc ms, iterations, handles)`` of every
    slice of the run, each number by the slice's share inside the union."""
    found = inside(run, "loop_acct")
    if found is None:
        return None
    out = []
    for _, piece, aux, note, share in found:
        f = fields(note)
        out.append(tuple(share * v for v in (
            aux, piece, float(f["late"]), float(f["gc"]), int(f["it"]),
            int(f["n"]))))
    return out


def per_operation(run, values):
    """The sum of ``values`` over the operations finished."""
    return sum(values) / len(run.ops)


def describe(run) -> list:
    """The account of the run in a few lines for a person."""
    import reduce_trace
    from dragonfly2_tpu.pkg import flight as flightlib

    acct, holds = slices(run), inside(run, "loop_lag")
    if acct is None:
        return []
    seconds = reduce_trace.total((op.t0, op.t1) for op in run.ops)
    turns = sum(it for *_, it, _ in acct)
    lines = [f"{len(acct)} slices, {turns:.0f} iterations "
             f"({turns / seconds:.0f} a second) of "
             f"{sum(n for *_, n in acct):.0f} handles in the "
             f"{seconds:.2f} s in which an operation was in flight"]
    by_who: dict = {}
    for _, _, aux, note, share in holds:
        who = fields(note).get("who", note)
        count, total = by_who.get(who, (0, 0.0))
        by_who[who] = (count + 1, total + aux * share)
    lines.append(f"{len(holds)} holds: " + ", ".join(
        f"{who} x{count} {total:.3f} s" for who, (count, total) in sorted(
            by_who.items(), key=lambda kv: -kv[1][1])[:8]))
    # The six longest, and every one that was a wedge.
    longest = sorted(holds, key=lambda h: -h[2])
    for t, cpu_ms, aux, note, _ in sorted(
            longest[:6] + [h for h in longest[6:] if h[2] >= 0.25]):
        op = min(run.ops, key=lambda op: max(op.t0 - t, t - aux - op.t1))
        lines.append(
            f"hold of {aux:.4f} s, cpu {cpu_ms} ms, {note}; it ended "
            f"{t - op.t0:.3f} s into operation {op.number} "
            f"({op.t1 - op.t0:.3f} s)")
    rings = {id(tf): tf for tf in (
        flightlib.recorder().get(op.task_id) for op in run.ops)
        if tf is not None}.values()
    stamped = sum(1 for tf in rings for e in tf.events()
                  if e[1] == flightlib.EV_LOOP_LAG)
    lines.append(
        f"the operations' own flights: {len(rings)} rings, events_dropped "
        f"{sum(tf.events_dropped for tf in rings)} in all, the fullest "
        f"{max((tf.events_total for tf in rings), default=0)} events; "
        f"loop_lag events among them {stamped}")
    return lines
