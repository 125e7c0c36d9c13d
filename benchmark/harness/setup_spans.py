"""The program's own spans of a job's set-up and end, from its telemetry
stream: one ``setup_span`` event per closed span (``name``, ``t0`` on the
clock of every event's ``ts``, ``dur_s``, ``parent``, ``job``; the
program's docs/Observability.md). What the nine readers of
``benchmark/layers`` that open ``setup_s`` share. A program without the
spans (the parent of the PR that brought them) gives every reader
nothing to read: each then returns None."""
from __future__ import annotations

from . import monitor

# jax stamps its phases with time.time() at both ends; a program span's
# end is its start plus a perf_counter duration
SLACK_S = 5e-3


def spans(run) -> list:
    """The ``setup_span`` events of the run's job (one ``lgb.train`` a
    run; were there several, the last ``train`` span names the job)."""
    found = monitor.of_kind(run.events, "setup_span")
    jobs = [s["job"] for s in found if s["name"] == "train"]
    return [s for s in found if not jobs or s["job"] == jobs[-1]]


def setup_end(run):
    """``ts`` of the last warm-up chunk's ``megastep`` event, where
    ``setup_s`` ends; None when the run did not get that far."""
    mega = monitor.of_kind(run.events, "megastep")
    warm = int(run.traffic["warmup_chunks"])
    return mega[warm - 1]["ts"] if 0 < warm <= len(mega) else None


def total(run, *names, parent=None, before=None):
    """Seconds in the spans of these names (under ``parent`` only, and
    started before ``before``, where given); None without any."""
    took = [s["dur_s"] for s in spans(run) if s["name"] in names
            and (parent is None or s["parent"] == parent)
            and (before is None or s["t0"] < before)]
    return sum(took) if took else None


def first_call_phase(run, phase: str):
    """Seconds in ``first_call/<phase>`` over every first call of a step
    that started inside the set-up (two in a job with two bodies). Only
    the spans whose parent is ``first_call``: an inner jit's trace lies
    inside the step's own and would count twice."""
    end = setup_end(run)
    if end is None:
        return None
    return total(run, "first_call/" + phase, parent="first_call",
                 before=end)


def leaves(found: list) -> list:
    """The spans that hold no other: no span names them as its parent
    from inside their interval."""
    def holds(a, b):
        return b["parent"] == a["name"] and b is not a \
            and a["t0"] - SLACK_S <= b["t0"] \
            and b["t0"] + b["dur_s"] <= a["t0"] + a["dur_s"] + SLACK_S
    return [a for a in found if not any(holds(a, b) for b in found)]


def union_s(intervals: list, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the intervals cover."""
    covered, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            covered += b - a
            reach = b
    return covered


def warmup_chunks(run) -> list:
    """Each warm-up chunk's own interval: from the end of the step's
    first call inside it (or, without one, the chunk before it) to its
    ``megastep`` event."""
    mega = monitor.of_kind(run.events, "megastep")
    warm = min(int(run.traffic["warmup_chunks"]), len(mega))
    calls = [s["t0"] + s["dur_s"] for s in spans(run)
             if s["name"] == "first_call"]
    out, before = [], None
    for chunk in mega[:warm]:
        inside = [t for t in calls if t <= chunk["ts"]
                  and (before is None or t > before)]
        start = max(inside) if inside else before
        if start is not None:
            out.append((start, chunk["ts"]))
        before = chunk["ts"]
    return out
