"""The program's own span and counter recorder: a flight recorder on the
host, on the profiler's clock (DESIGN.md §18).

Each record is ``(name, start_ns, end_ns, span_id, cause_id, attrs)``:

  name      ``serve.step``, ``serve.stage``, ... (``ConvServer``), or a
            compile event (below)
  start_ns, end_ns
            ``time.time_ns()`` stamps: the wall clock the profiler's
            ``profile_start_time`` is on, whatever clock a caller injects
            for its own deadlines
  span_id   the record's own id (0 when nothing points at it)
  cause_id  the id of the span that caused it (a step's phases name the
            step; 0 for none)
  attrs     a small dict, or ``None``

Records go into one process-wide bounded ring: each costs O(1), memory
cannot grow, and the oldest record is overwritten once the ring is full.
``dropped`` counts the overwritten records and ``lost_ns`` is the latest
end stamp among them, so a reader knows whether its window lost any.  The
ring holds :data:`RING` records, four times the roughly 16k that a 30 s
window of 128x128 serving steps (about 90 a second, six records each)
writes.  Recording is always on, like a flight recorder; :func:`spans`
returns the records oldest first.

Compile events: a ``jax.monitoring`` listener, registered once per process
when this module is first imported, records each jaxpr trace
(``compile.trace``) and each backend compile (``compile.backend``) with the
stamps JAX gives them.  So "which step recompiled" has an answer: the
compile's stamps fall inside that step's span.
"""
from __future__ import annotations

import itertools
import time
from typing import Optional

import jax

__all__ = ["RING", "COMPILE_EVENTS", "Recorder", "recorder", "record",
           "new_id", "now", "spans", "clear"]

RING = 1 << 16

# jax.monitoring event name -> record name
_JAX_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "compile.trace",
              "/jax/core/compile/backend_compile_duration": "compile.backend"}
COMPILE_EVENTS = ("compile.trace", "compile.backend")

now = time.time_ns


class Recorder:
    """A bounded ring of span records (see the module docstring)."""

    def __init__(self, size: int = RING):
        if size < 1:
            raise ValueError(f"ring size must be positive, got {size}")
        self.size = int(size)
        self.clear()

    def clear(self):
        self._buf = [None] * self.size
        self._n = 0
        self.dropped = 0
        self.lost_ns = -1

    def record(self, name: str, start_ns: int, end_ns: int, span_id: int = 0,
               cause_id: int = 0, attrs: Optional[dict] = None):
        i = self._n
        self._n = i + 1
        slot = i % self.size
        if i >= self.size:
            self._drop(self._buf[slot][2])
        self._buf[slot] = (name, start_ns, end_ns, span_id, cause_id, attrs)

    def _drop(self, end_ns: int):
        self.dropped += 1
        if end_ns > self.lost_ns:
            self.lost_ns = end_ns

    def spans(self) -> list:
        """The records still in the ring, oldest first."""
        n = self._n
        if n <= self.size:
            return self._buf[:n]
        k = n % self.size
        return self._buf[k:] + self._buf[:k]


_REC = Recorder()
_ids = itertools.count(1)


def recorder() -> Recorder:
    """The process-wide recorder."""
    return _REC


def record(name: str, start_ns: int, end_ns: int, span_id: int = 0,
           cause_id: int = 0, attrs: Optional[dict] = None):
    _REC.record(name, start_ns, end_ns, span_id, cause_id, attrs)


def new_id() -> int:
    """A fresh span id (never 0), for a span whose children record first."""
    return next(_ids)


def spans() -> list:
    return _REC.spans()


def clear():
    _REC.clear()


def _on_time_span(event: str, start_s: float, end_s: float, **kw):
    name = _JAX_SPANS.get(event)
    if name is not None:
        _REC.record(name, int(start_s * 1e9), int(end_s * 1e9),
                    attrs={"fun": kw.get("fun_name")})


jax.monitoring.register_event_time_span_listener(_on_time_span)
