"""The program's own spans inside the traced window.

The serving tier and the training step record spans and compile events in
a process-wide recorder (``repro.utils.spans`` in the program): records
``(name, start_ns, end_ns, span_id, cause_id, attrs)`` on
``time.time_ns()``, the clock of the benchmark's own spans.  The readers of
the per-layer metrics that time the serving step by phase, and those that
count compiles, take the records that overlap the ``bench.window`` span
from here.  Each returns ``None``, and never raises, when the run was
not traced, holds no window, the program has no recorder, or the
recorder's ring overwrote records of the window.
"""
from __future__ import annotations

import statistics

from chipbench import trace as tracing

COMPILE_EVENTS = ("compile.trace", "compile.backend")


def window_records(run):
    """-> ``(w0, w1, records)`` of the traced window, or ``None``."""
    if run.trace is None:
        return None
    wins = [(a, b) for n, a, b in run.spans.spans if n == tracing.WINDOW]
    if not wins:
        return None
    try:
        from repro.utils import spans
    except ImportError:
        return None
    rec = spans.recorder()
    w0, w1 = wins[0]
    if rec.lost_ns >= w0:
        return None
    return w0, w1, [r for r in rec.spans() if r[1] <= w1 and r[2] >= w0]


def median_ms(run, name: str):
    """Median duration of the window's ``name`` spans, in ms."""
    got = window_records(run)
    if got is None:
        return None
    durs = [r[2] - r[1] for r in got[2] if r[0] == name]
    return statistics.median(durs) / 1e6 if durs else None


def host_share(run):
    """Percent of the window inside ``serve.step`` spans but outside their
    ``serve.forward`` spans, each clipped to the window."""
    got = window_records(run)
    if got is None:
        return None
    w0, w1, recs = got
    inside = {"serve.step": 0, "serve.forward": 0}
    for r in recs:
        if r[0] in inside:
            inside[r[0]] += max(0, min(r[2], w1) - max(r[1], w0))
    if not inside["serve.step"]:
        return None
    return 100.0 * (inside["serve.step"] - inside["serve.forward"]) / (w1 - w0)


def compiles(run):
    """Jaxpr traces and backend compiles that overlap the window."""
    got = window_records(run)
    if got is None:
        return None
    return sum(r[0] in COMPILE_EVENTS for r in got[2])
