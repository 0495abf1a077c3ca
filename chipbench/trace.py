"""Device numbers from a profiler trace.

``Tracer`` records the measured window of a ``--trace 1`` run with the JAX
profiler into a temporary directory, reduces it with :func:`reduce_planes`
and deletes it.  The profiler traces the device alone: host tracing would
record every chunk of the runtime's host-side relayout of each input batch,
which made a 128x128 serving step ten times slower on the chip.  The
benchmark's own host spans (:class:`Spans`, on the wall clock) are placed on
the trace's clock by its ``profile_start_time``.  The reduction reads, per
chip, the device plane's op events inside the ``bench.window`` span:

  busy      the union of the op intervals (overlapping ops count once)
  ops       seconds per HLO instruction, summed
  families  per kernel family (``chipbench/kernels.json``, keyed by the
            instruction name less its ``.N`` suffix): events, seconds
  gaps      each idle interval, named after the innermost ``bench.*`` host
            span that covers its midpoint (``bench.window`` when only the
            window does)

Chip numbers are averaged over the chips traced.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
import time

WINDOW = "bench.window"


class Spans:
    """The benchmark's own host spans: ``(name, start_ns, end_ns)`` on the
    wall clock, which the profiler's ``profile_start_time`` is also on."""

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))

    def plane(self, start_ns: int) -> dict:
        """The spans as a host plane, relative to the profile's start."""
        return {"name": "/host:bench", "lines": [{"name": "bench", "events": [
            (n, a - start_ns, b - a) for n, a, b in self.spans]}]}


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    ops: dict            # op name -> seconds (mean over chips)
    families: dict       # family -> {"events": n, "seconds": s} (mean)
    gaps: dict           # host span -> idle seconds (mean over chips)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(mid, spans):
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else WINDOW


def op_name(event: str) -> str:
    """A device event's HLO instruction name: the trace names an op event
    by its HLO text (``%pad.68 = bf16[...] pad(...)``)."""
    if event.startswith("%"):
        return event[1:].split(" ", 1)[0]
    return event


def base_name(op: str) -> str:
    """``pointwise_dgrad_pallas.13`` -> ``pointwise_dgrad_pallas``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def device_lines(planes):
    """-> [(plane name, op events)] for each device plane's op line."""
    out = []
    for pl in planes:
        if not pl["name"].startswith("/device:TPU:"):
            continue
        for ln in pl["lines"]:
            if ln["name"] == "XLA Ops":
                out.append((pl["name"], ln["events"]))
    return out


def reduce_planes(planes, families: dict) -> Reduced:
    """Reduce planes given as ``[{"name", "lines": [{"name", "events":
    [(name, start_ns, dur_ns)]}]}]`` (what :func:`load` returns)."""
    spans = [(s, s + d, n) for pl in planes if pl["name"].startswith("/host")
             for ln in pl["lines"] for n, s, d in ln["events"]
             if n.startswith("bench.")]
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if not wins:
        raise ValueError("chipbench: the trace holds no bench.window span")
    w0, w1 = wins[0]
    inner = [sp for sp in spans if sp[2] != WINDOW]
    devs = device_lines(planes)
    if not devs:
        raise ValueError("chipbench: the trace holds no device op line")
    busy, ops, fams, gaps = 0.0, {}, {}, {}
    for _, events in devs:
        ivs = []
        for event, s, d in events:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            name = op_name(event)
            ops[name] = ops.get(name, 0.0) + (e - s)
            fam = families.get(base_name(name))
            if fam is not None:
                f = fams.setdefault(fam, {"events": 0, "seconds": 0.0})
                f["events"] += 1
                f["seconds"] += (e - s)
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                lab = _label((gs + ge) / 2, inner)
                gaps[lab] = gaps.get(lab, 0.0) + (ge - gs)
    n = len(devs)
    ns = 1e-9 / n
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * ns, chips=n,
        ops={k: v * ns for k, v in ops.items()},
        families={k: {"events": v["events"] / n, "seconds": v["seconds"] * ns}
                  for k, v in fams.items()},
        gaps={k: v * ns for k, v in gaps.items()})


def load(path: str):
    """An ``.xplane.pb`` file as plain planes (see :func:`reduce_planes`),
    each with its ``stats``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [{"name": pl.name, "stats": dict(pl.stats),
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in pl.lines]}
            for pl in pd.planes]


def profile_start_ns(planes) -> int:
    for pl in planes:
        if "profile_start_time" in pl.get("stats", {}):
            return int(pl["stats"]["profile_start_time"])
    raise ValueError("chipbench: the trace holds no profile_start_time")


class Tracer:
    """Profile the measured window into a temporary directory."""

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self, spans: Spans, families: dict) -> Reduced:
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise ValueError("chipbench: the profiler wrote no trace")
            planes = load(paths[0])
            planes.append(spans.plane(profile_start_ns(planes)))
            return reduce_planes(planes, families)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
