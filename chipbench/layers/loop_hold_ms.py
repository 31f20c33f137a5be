"""Event loop: what every other coroutine waited behind: the summed ms of the
``loop_lag`` holds of kind ``held`` (ONE turn of 20 ms and more; the note's
``who`` is the frame the sampler met inside it), each clipped to the union of
the operations, over the operations finished. A late wake (``late``) is no
hold of anybody's and is left to ``loop_late_ms``."""

from layers import loop_events


def read(run):
    holds = loop_events.inside(run, "loop_lag")
    if holds is None:
        return None
    for line in loop_events.describe(run):
        print(f"[chipbench] loop: {line}", flush=True)
    return loop_events.per_operation(
        run, (aux * share * 1000.0 for _, _, aux, note, share in holds
              if note.startswith("held")))
