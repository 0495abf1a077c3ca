"""The conv serving tier: 2-D (data x model) sharded blocked-CNN inference
behind a continuous-batching front door (DESIGN.md §15).

Two mesh axes, two paper facts:

  * ``data`` — batch entries are trivially parallel: each device blocks its
    own sub-batch once at entry and chains every layer in
    ``[n/D, C/Cb, H, W, Cb]`` with zero repacks and zero collectives.
  * ``model`` — the paper's §3.2 observation that output channels partition
    into independent ``Co/Cob`` blocks *is* a model axis: shard the stored
    weight's leading ``Co/Cob`` dim, run the **unmodified** blocked kernel
    per shard over ``co / M`` output channels, and ``all_gather`` the
    blocked channel dim once per layer boundary (the next layer consumes
    full Ci).  Each shard computes its channels with the identical
    reduction order as the single-device kernel, so the sharded forward is
    bit-identical — the property ``tests/test_conv_serve_tier.py`` pins.

``jax.shard_map`` rather than jit-with-shardings:
the per-shard program is *exactly* the single-device program, so the Pallas
kernel runs per shard with per-shard blocked layouts — no global-view
resharding can be introduced behind the kernel's back, and each shard's
convs resolve their *per-shard* dispatch key (``DispatchKey.shard``: batch
over data, Co over model) through the measured table.

``ConvServer`` fronts the mesh for ragged traffic: requests carry arbitrary
image sizes, a ``SpatialBucketer`` groups them onto a small set of
dispatch-table-tuned ``(H, W)`` buckets (pad on entry, one compiled
executable per bucket), a per-bucket ``SlotPool`` does continuous-batching
admission, and the server reports per-request latency plus achieved batch
occupancy (``chipbench/serve.py`` drives it open-loop on the chip).  Each
step also records its phases as spans in the process-wide recorder
(``repro.utils.spans``; DESIGN.md §18).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.context import ConvContext, as_context, reject_legacy_kwargs
from repro.core.errors import TransientError
from repro.core.layout import nhwc_to_blocked
from repro.nn.conv import BlockedConv2D
from repro.serve.scheduler import (ConvRequest, Outcome, SlotPool,
                                   SpatialBucketer)
from repro.utils import spans as _spans
from repro.utils.faults import inject as _inject_fault

__all__ = ["make_sharded_cnn_forward", "sharded_cnn_predict",
           "co_shard_convs", "BreakerState", "ConvServer"]


def co_shard_convs(model, m: int):
    """Per-shard layers for Co-block sharding of width ``m`` — or raise.

    The per-shard program must be the unmodified blocked kernel, which
    holds only when every layer keeps its *pencils* under the shard: the
    weight is sharded on its leading ``Co/Cob`` dim in whole blocks, so the
    shard's layout choice for ``co / m`` channels must reproduce the full
    model's ``cb_out`` (counterexample: ``co=24, lane=8, m=2`` — the full
    layout picks an 8-pencil but 12 channels pick 6, so shard block
    boundaries would not be weight block boundaries).  Dense-only: a
    grouped conv's block-diagonal weight shards over *groups*, a different
    partitioning this tier does not implement.
    """
    shards = []
    for i, conv in enumerate(model.convs):
        if not isinstance(conv, BlockedConv2D) or conv.groups != 1:
            raise ValueError(
                f"conv{i}: model-axis (Co) sharding is dense-only; "
                "grouped/depthwise layers shard over data only")
        if conv.co % m:
            raise ValueError(
                f"conv{i}: model axis {m} must divide co={conv.co}")
        shard = dataclasses.replace(conv, co=conv.co // m)
        if shard.out_pencil != conv.out_pencil:
            raise ValueError(
                f"conv{i}: co={conv.co} over model={m} changes the output "
                f"pencil ({conv.out_pencil} -> {shard.out_pencil}); shard "
                "boundaries must fall on whole Co blocks — pick co, lane "
                "and mesh so cb_out divides co/m")
        if shard.in_pencil != conv.in_pencil:
            raise ValueError(
                f"conv{i}: sharding changes the input pencil "
                f"({conv.in_pencil} -> {shard.in_pencil})")
        shards.append(shard)
    return tuple(shards)


def make_sharded_cnn_forward(model, mesh, axis: str = "data", *,
                             model_axis: Optional[str] = None,
                             context: Optional[ConvContext] = None,
                             **legacy):
    """-> jitted ``f(params, x_nhwc) -> logits`` over a 1- or 2-axis mesh.

    ``axis`` shards the batch (params replicated along it); ``model_axis``
    additionally Co-shards every conv's weight + bias on their leading
    ``Co/Cob`` block dim, with one tiled ``all_gather`` of the blocked
    channel dim per layer boundary (the next layer needs full Ci; the head
    needs the full pooled feature).  The batch dim must be divisible by the
    data width (use :func:`sharded_cnn_predict` for ragged batches) and
    every ``co`` by the model width in whole output blocks
    (:func:`co_shard_convs` validates).

    Inside a shard the forward is the unmodified single-device program, so
    layouts, tiling and the fused epilogue are per-shard — and so is conv
    routing: each shard's convs resolve their *per-shard* geometry
    (``DispatchKey.shard``) through the dispatch subsystem.  Routing
    happens at trace time, so the decision is baked into the compiled
    executable — re-tune, re-make to pick up new winners.

    ``context`` is the one execution-context object (``ConvContext``) —
    the only spelling; the old loose kwargs raise the migration TypeError.
    Memoized on ``(model, mesh, axis, model_axis, context)`` — all
    frozen/hashable (a ``ConvDispatcher`` hashes by identity) — so a
    serving loop calling this per batch reuses one jitted function and
    hits the compile cache instead of retracing every request.
    """
    reject_legacy_kwargs("make_sharded_cnn_forward", legacy)
    ctx = as_context(context)
    return _make_sharded_cnn_forward(model, mesh, axis, model_axis, ctx)


@functools.lru_cache(maxsize=None)
def _make_sharded_cnn_forward(model, mesh, axis: str,
                              model_axis: Optional[str],
                              ctx: ConvContext):
    if model_axis is None:
        def fwd(p, x):
            return model(p, x, context=ctx)

        sharded = jax.shard_map(fwd, mesh=mesh, in_specs=(P(), P(axis)),
                                out_specs=P(axis), check_vma=False)
        return jax.jit(sharded)

    m = mesh.shape[model_axis]
    shard_convs = co_shard_convs(model, m)
    last = len(shard_convs) - 1

    def fwd(p, x):
        # the single layout transform, then per-shard blocked layers; the
        # gather re-concatenates Co blocks in shard order = blocked channel
        # order (shard k holds the contiguous block range [k*B/m, (k+1)*B/m))
        h = nhwc_to_blocked(x, shard_convs[0].in_pencil)
        for i, conv in enumerate(shard_convs):
            h = conv(p[f"conv{i}"], h, context=ctx, gap=(i == last))
            # non-last layers gather the blocked dim [N, C/Cb, H, W, Cb];
            # the last layer's fused GAP emitted [N, co/m], gathered to the
            # full pooled feature — axis 1 is the channel dim either way
            h = jax.lax.all_gather(h, model_axis, axis=1, tiled=True)
        return h @ p["head"].astype(h.dtype)

    pspecs = {f"conv{i}": P(model_axis) for i in range(len(shard_convs))}
    pspecs["head"] = P()
    sharded = jax.shard_map(fwd, mesh=mesh, in_specs=(pspecs, P(axis)),
                            out_specs=P(axis), check_vma=False)
    return jax.jit(sharded)


def sharded_cnn_predict(model, params, x_nhwc, mesh, axis: str = "data", *,
                        model_axis: Optional[str] = None,
                        context: Optional[ConvContext] = None,
                        **legacy):
    """Serve one (possibly ragged) batch: pad N up to a multiple of the data
    axis, run the sharded forward, slice the padding back off.  Degenerate
    tiny batches — where the zero padding would outnumber the real rows
    (``pad >= n``) — route to the single-device forward instead of burning
    most of the mesh on computing zeros."""
    reject_legacy_kwargs("sharded_cnn_predict", legacy)
    ctx = as_context(context)
    n = x_nhwc.shape[0]
    width = mesh.shape[axis]
    pad = (-n) % width
    if pad >= n:
        return model(params, x_nhwc, context=ctx)
    if pad:
        x_nhwc = jnp.concatenate(
            [x_nhwc, jnp.zeros((pad,) + x_nhwc.shape[1:], x_nhwc.dtype)])
    f = make_sharded_cnn_forward(model, mesh, axis, model_axis=model_axis,
                                 context=ctx)
    logits = f(params, x_nhwc)
    return logits[:n]


class BreakerState(str, enum.Enum):
    """Per-bucket circuit-breaker states (DESIGN.md §16).

    CLOSED -> primary (Pallas-routed) executable; OPEN -> the bucket is
    demoted to the jnp executable (bit-identical — ``EXACT_IMPLS``);
    HALF_OPEN -> the cooldown elapsed and the next step re-probes the
    primary once (success closes, failure re-opens).
    """

    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"


class _Breaker:
    """One bucket's breaker: counts *consecutive exhausted steps* (a step
    whose primary attempt burned every retry), opens at ``threshold``,
    re-probes after ``cooldown`` engine steps."""

    def __init__(self, threshold: int, cooldown: int):
        self.threshold, self.cooldown = int(threshold), int(cooldown)
        self.state = BreakerState.CLOSED
        self.failures = 0                # consecutive exhausted steps
        self.opened_at = -1              # step index of the last open

    def allow_primary(self, step_idx: int) -> bool:
        if self.state is BreakerState.CLOSED:
            return True
        if (self.state is BreakerState.OPEN
                and step_idx - self.opened_at >= self.cooldown):
            self.state = BreakerState.HALF_OPEN
        return self.state is BreakerState.HALF_OPEN

    def record_success(self):
        self.state = BreakerState.CLOSED
        self.failures = 0

    def record_exhausted(self, step_idx: int):
        self.failures += 1
        if (self.state is BreakerState.HALF_OPEN
                or self.failures >= self.threshold):
            self.state = BreakerState.OPEN
            self.opened_at = step_idx


class ConvServer:
    """Continuous-batching front door over the (data x model) mesh.

    One compiled executable per ``(H, W)`` bucket (batch dim fixed at
    ``batch``); arbitrary-size requests pad up to their bucket on admission
    and run whenever their bucket has filled slots — a partially-filled
    step pads the batch with zero rows rather than waiting (latency over
    occupancy; the occupancy number reports the cost of that choice).

    ``clock`` is injectable: the chip benchmark (``chipbench/serve.py``)
    passes ``time.perf_counter`` so latencies are real; tests pass a
    deterministic counter so the slot/occupancy accounting is exact.  The
    clock stamps requests and deadlines only: the spans of
    :meth:`step` are stamped with ``time.time_ns()`` whatever it is.

    Fault tolerance (DESIGN.md §16) — every submitted request terminates
    in the :class:`~repro.serve.scheduler.Outcome` lattice:

      * **deadlines** — ``submit(req, timeout=...)`` stamps an absolute
        deadline on the injected clock; each step sweeps expired *queued*
        requests out as ``TIMED_OUT`` before admission, so a stale request
        never occupies a slot.
      * **backpressure** — ``max_queue`` bounds each bucket's queue;
        a full queue sheds the submission as ``REJECTED`` immediately
        (the caller learns synchronously, no silent buildup).
      * **retries** — a ``TransientError`` from a step (fault injection,
        ``VmemMisfitError``, a real launch failure) retries up to
        ``max_retries`` times with capped exponential backoff on the
        injectable ``sleep``.
      * **degradation** — a step that exhausts its retries runs the jnp
        executable instead: same context with ``impl="jnp"``, which is in
        ``EXACT_IMPLS`` — bit-identical logits, never injected (the
        escape hatch must not fault).  A per-bucket circuit breaker counts
        consecutive exhausted steps, opens at ``breaker_threshold`` (the
        bucket then skips the primary entirely), and half-opens after
        ``breaker_cooldown`` steps to re-probe.
      * **observability** — :meth:`health` snapshots queue depth, shed
        rate, outcome counters, retries, per-bucket occupancy and breaker
        state; :meth:`step` records each batch's phases as spans
        (``repro.utils.spans``).

    ``FatalError``s (and any non-transient exception) still propagate:
    retrying a programmer error repeats it.
    """

    def __init__(self, model, params, mesh,
                 buckets: Sequence[Tuple[int, int]], batch: int, *,
                 axis: str = "data", model_axis: Optional[str] = None,
                 context: Optional[ConvContext] = None,
                 clock=time.monotonic,
                 max_queue: Optional[int] = None,
                 max_retries: int = 2,
                 backoff: float = 0.0, max_backoff: float = 0.05,
                 breaker_threshold: int = 3, breaker_cooldown: int = 8,
                 sleep=time.sleep):
        if batch % mesh.shape[axis]:
            raise ValueError(
                f"server batch {batch} must be divisible by the data axis "
                f"width {mesh.shape[axis]}")
        self.model, self.params, self.mesh = model, params, mesh
        self.axis, self.model_axis = axis, model_axis
        self.context = as_context(context)
        self.batch = int(batch)
        self.bucketer = SpatialBucketer(buckets)
        self.pool = SlotPool(self.bucketer.buckets, self.batch,
                             max_queue=max_queue)
        self.clock = clock
        self.completed: list = []
        self.max_retries = int(max_retries)
        self.backoff, self.max_backoff = float(backoff), float(max_backoff)
        self._sleep = sleep
        self._step_idx = 0
        self._breakers = {b: _Breaker(breaker_threshold, breaker_cooldown)
                          for b in self.bucketer.buckets}
        self._counters = {
            "submitted": 0, "ok": 0, "shed": 0, "timed_out": 0,
            "retries": 0, "transient_faults": 0, "degraded_steps": 0,
            "admit_faults": 0,
        }
        # the forward's input sharding: each step puts its staged batch
        # there once, so retries and the degraded executable reuse it
        self._in_sharding = NamedSharding(mesh, P(axis))
        self._fwd = make_sharded_cnn_forward(
            model, mesh, axis, model_axis=model_axis, context=self.context)
        # the degraded executable: identical context demoted to the jnp
        # impl — EXACT_IMPLS membership makes it bit-identical to the
        # Pallas routes, which is what licenses silent demotion
        self._fwd_jnp = make_sharded_cnn_forward(
            model, mesh, axis, model_axis=model_axis,
            context=dataclasses.replace(self.context, impl="jnp"))

    def warmup(self):
        """Trace + compile every bucket's executable on zero batches, so the
        first real request's latency is service time, not compile time (the
        bench calls this before starting its trace).  Warms the degraded
        (jnp) executable too — a breaker trip must not pay a compile.  The
        zero batches go in as :meth:`step` puts its batches, on the input
        sharding, so the executables warmed are the ones a step calls."""
        ci = self.model.convs[0].ci
        for bh, bw in self.bucketer.buckets:
            x = jax.device_put(np.zeros((self.batch, bh, bw, ci), np.float32),
                               self._in_sharding)
            jax.block_until_ready(self._fwd(self.params, x))
            jax.block_until_ready(self._fwd_jnp(self.params, x))

    # -- queue management --------------------------------------------------
    def submit(self, req: ConvRequest, *,
               timeout: Optional[float] = None) -> "Outcome":
        """Queue one request; -> its outcome so far (PENDING, or REJECTED
        when its bucket's bounded queue is full — synchronous shed).
        ``timeout`` (seconds on the server clock) derives ``req.deadline``
        from the submit stamp; a pre-set absolute ``req.deadline`` rides
        through untouched."""
        h, w = req.image.shape[:2]
        req.bucket = self.bucketer.bucket_for(h, w)
        req.t_submit = self.clock()
        if timeout is not None:
            req.deadline = req.t_submit + timeout
        self._counters["submitted"] += 1
        if not self.pool.enqueue(req):
            req.outcome, req.done, req.t_done = (
                Outcome.REJECTED, True, req.t_submit)
            self._counters["shed"] += 1
            self.completed.append(req)
        return req.outcome

    def _expire(self):
        """Sweep queued requests past deadline out as TIMED_OUT — they
        complete without ever occupying a slot."""
        t = self.clock()
        for r in self.pool.sweep(
                lambda r: r.deadline is not None and r.deadline <= t):
            r.outcome, r.done, r.t_done = Outcome.TIMED_OUT, True, t
            r.logits = None
            self._counters["timed_out"] += 1
            self.completed.append(r)

    # -- one engine step ---------------------------------------------------
    def _execute(self, bucket, x):
        """One batched forward of the device batch ``x`` with the full
        degradation ladder: primary (retry transient failures with capped
        backoff, breaker permitting) then the bit-identical jnp executable.
        Always returns logits, ready on the device — only a
        ``FatalError``/foreign exception escapes."""
        br = self._breakers[bucket]
        if br.allow_primary(self._step_idx):
            for attempt in range(self.max_retries + 1):
                try:
                    _inject_fault("serve.step")
                    out = jax.block_until_ready(self._fwd(self.params, x))
                    br.record_success()
                    return out
                except TransientError:
                    self._counters["transient_faults"] += 1
                    if attempt < self.max_retries:
                        self._counters["retries"] += 1
                        if self.backoff > 0.0:
                            self._sleep(min(self.backoff * 2 ** attempt,
                                            self.max_backoff))
            br.record_exhausted(self._step_idx)
        self._counters["degraded_steps"] += 1
        return jax.block_until_ready(self._fwd_jnp(self.params, x))

    def step(self) -> bool:
        """One engine step: expire stale queued requests, admit into free
        slots, then run one batched forward per non-empty bucket through
        the degradation ladder.  -> ran anything.

        Each batch records a ``serve.step`` span (attrs ``step``,
        ``bucket``, ``rows`` real and ``executed`` padded) and, with it as
        cause, its five phases in order: ``serve.stage`` (the step's expiry
        sweep and admission for its first batch, then drain, pad, stack and
        zero fill), ``serve.h2d`` (the staged batch put on the forward's
        input sharding, waited for), ``serve.forward`` (the executable up
        to ready logits, across retries; attrs ``path`` and ``attempts``),
        ``serve.readback`` (the logits to the host) and ``serve.complete``
        (stamping the requests)."""
        now, record, c = _spans.now, _spans.record, self._counters
        t_stage = now()
        self._expire()
        try:
            self.pool.admit()
        except TransientError:
            # queues are untouched on an admission fault — the requests
            # simply wait one step and admission retries
            self._counters["admit_faults"] += 1
        ran = False
        for bucket in self.bucketer.buckets:
            reqs = self.pool.drain(bucket)
            if not reqs:
                continue
            ran = True
            sid = _spans.new_id()
            imgs = np.stack([self.bucketer.pad(r.image, bucket)
                             for r in reqs])
            if len(reqs) < self.batch:      # zero rows up to the executable
                fill = np.zeros((self.batch - len(reqs),) + imgs.shape[1:],
                                imgs.dtype)
                imgs = np.concatenate([imgs, fill])
            t_h2d = now()
            x = jax.block_until_ready(
                jax.device_put(imgs, self._in_sharding))
            faults, degraded = c["transient_faults"], c["degraded_steps"]
            t_fwd = now()
            out = self._execute(bucket, x)
            t_read = now()
            logits = np.asarray(out)
            t_done = now()
            t = self.clock()
            for i, r in enumerate(reqs):    # batch-level exit slice
                r.logits, r.t_done, r.done = logits[i], t, True
                r.outcome = Outcome.OK
                c["ok"] += 1
                self.completed.append(r)
            t_end = now()
            record("serve.stage", t_stage, t_h2d, 0, sid)
            record("serve.h2d", t_h2d, t_fwd, 0, sid)
            record("serve.forward", t_fwd, t_read, 0, sid,
                   attrs={"path": ("degraded" if c["degraded_steps"] > degraded
                                   else "primary"),
                          "attempts": c["transient_faults"] - faults + 1})
            record("serve.readback", t_read, t_done, 0, sid)
            record("serve.complete", t_done, t_end, 0, sid)
            record("serve.step", t_stage, t_end, sid, 0,
                   attrs={"step": self._step_idx, "bucket": bucket,
                          "rows": len(reqs), "executed": self.batch})
            t_stage = now()
        self._step_idx += 1
        return ran

    def run(self, max_steps: int = 10 ** 6):
        steps = 0
        while self.pool.pending and steps < max_steps:
            self.step()
            steps += 1
        if self.pool.pending:               # expired stragglers at the cap
            self._expire()
        return self.completed

    # -- reporting ---------------------------------------------------------
    def occupancy(self, bucket: Optional[Tuple[int, int]] = None) -> float:
        return self.pool.occupancy(bucket)

    def latencies(self, bucket: Optional[Tuple[int, int]] = None
                  ) -> np.ndarray:
        """Latencies of *served* requests (outcome OK) — shed/timed-out
        requests report through :meth:`health`, not the latency tail."""
        return np.array([r.latency for r in self.completed
                         if r.outcome is Outcome.OK
                         and (bucket is None or r.bucket == bucket)],
                        np.float64)

    def health(self) -> dict:
        """One observability snapshot: queue/outcome/fault counters plus
        per-bucket occupancy and breaker state (read by the chip benchmark,
        ``chip_smoke.py`` and the tests).  Cheap enough to call every step:
        span summaries live in the recorder, not here."""
        c = dict(self._counters)
        sub = max(c["submitted"], 1)
        return {
            **c,
            "steps": self._step_idx,
            "queue_depth": self.pool.queue_depth,
            "pending": self.pool.pending,
            "shed_rate": c["shed"] / sub,
            "timeout_rate": c["timed_out"] / sub,
            "occupancy": {f"{h}x{w}": self.pool.occupancy((h, w))
                          for h, w in self.bucketer.buckets},
            "breakers": {f"{h}x{w}": self._breakers[(h, w)].state.value
                         for h, w in self.bucketer.buckets},
        }
