"""Serving-tier load generator: tail latency + occupancy under ragged traffic.

Drives ``repro.serve.ConvServer`` — the continuous-batching front door over
the (data x model) mesh (DESIGN.md §15) — with a synthetic heavy-traffic
trace: a seeded stream of variable-size image requests arriving in bursts
between engine steps, so buckets run partially full exactly the way real
admission does.  Per bucket it reports p50/p99 request latency (submit ->
logits, wall clock, compile excluded via warmup) and achieved batch
occupancy, in the ``BENCH_*``/``check_regression`` row schema: the ``serve``
section's ``*_us`` fields gate against ``BENCH_baseline.json`` in CI; the
occupancy column is the accounting (how much of each compiled batch was real
work).

It also records the routing: one ``dispatch`` row per (bucket, conv layer,
direction) with the **per-shard** key (``DispatchKey.shard`` — batch over
the data axis, Co over the model axis), which is the geometry each shard's
kernel actually resolves at trace time.  ``check_regression
--dispatch-table`` cross-references these rows for coverage, so a serve
bucket whose routing silently degraded is visible in the gate.

``--faults`` runs the seeded chaos trace instead (DESIGN.md §16): a
deterministic ``FaultPlan`` injects transient kernel-launch failures into a
fixed fraction of serve steps (plus occasional admission faults), every
k-th request carries an already-expired deadline, and the queue bound is
tightened so bursts shed.  Because the injection draws are stateless hashes
and the queue evolution never reads the wall clock, the outcome counters
(completed / shed / timed-out / retries / degraded steps) are bit-stable
across machines — the ``faults`` section's ``*_count``/``*_rate`` fields
gate *exactly* in ``check_regression``, while its degraded-mode p50/p99
gate like any other ``*_us`` field.

Runnable:  PYTHONPATH=src python -m benchmarks.bench_serve --smoke \
               [--json BENCH_ci.json]
           PYTHONPATH=src python -m benchmarks.bench_serve --smoke --faults \
               [--json BENCH_ci.json]
(``--json`` merges into an existing report file — the CI job appends the
serve section to fig_conv's output; the module sets the 8-host-device flag
itself, before jax initializes.)
"""
from __future__ import annotations

import argparse
import json
import os
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="serving-tier bench: p50/p99 latency + occupancy under "
                    "a synthetic ragged-traffic load")
    ap.add_argument("--smoke", action="store_true",
                    help="the pinned CI configuration (small model, test "
                         "mesh, deterministic trace)")
    ap.add_argument("--requests", type=int, default=48,
                    help="total requests in the synthetic trace")
    ap.add_argument("--batch", type=int, default=4,
                    help="slots per bucket (must be a multiple of the data "
                         "axis width)")
    ap.add_argument("--model-shard", type=int, default=1,
                    help="model-axis width (Co-block sharding; 1 = pure "
                         "data parallelism)")
    ap.add_argument("--burst", type=int, default=6,
                    help="mean requests arriving between engine steps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="write/merge the report into this JSON file")
    ap.add_argument("--faults", action="store_true",
                    help="run the seeded chaos trace: deterministic "
                         "transient-fault injection + deadlines + a tight "
                         "queue bound; emits the `faults` gate section")
    ap.add_argument("--fault-rate", type=float, default=0.15,
                    help="fraction of serve steps that draw a transient "
                         "kernel-launch failure (chaos mode)")
    ap.add_argument("--max-queue", type=int, default=6,
                    help="per-bucket queue bound in chaos mode (tight, so "
                         "bursts shed deterministically)")
    ap.add_argument("--deadline-every", type=int, default=7,
                    help="every k-th request carries an already-expired "
                         "deadline (deterministic TIMED_OUT)")
    return ap.parse_args(argv)


# The pinned CI buckets: the (H, W) shapes the serving tier compiles for.
# Changing these invalidates the serve section of BENCH_baseline.json —
# regenerate it in the same PR (same contract as fig_conv.CI_SHAPES).
CI_BUCKETS = [(12, 12), (16, 16)]


def build_smoke_model():
    """The CI serving model: small enough for an interpret-mode CPU runner,
    dense with lane-8 pencils so co=32 Co-shards over a model axis of 4
    (whole 8-pencil blocks per shard) without changing any layout."""
    from repro.nn.conv import BlockedCNN, BlockedConv2D
    return BlockedCNN(convs=(
        BlockedConv2D(ci=8, co=32, lane=8),
        BlockedConv2D(ci=32, co=32, stride=2, lane=8)), n_classes=10)


def synth_trace(rng, n_requests: int, buckets, ci: int):
    """The synthetic ragged load: image sizes drawn uniformly inside a
    random bucket (so every bucket sees traffic and padding is exercised),
    returned as a list of host images."""
    import numpy as np
    images = []
    for _ in range(n_requests):
        bh, bw = buckets[int(rng.integers(len(buckets)))]
        lo_h = 1 if bh <= min(b[0] for b in buckets) else \
            max(b[0] for b in buckets if b[0] < bh) + 1
        lo_w = 1 if bw <= min(b[1] for b in buckets) else \
            max(b[1] for b in buckets if b[1] < bw) + 1
        h = int(rng.integers(lo_h, bh + 1))
        w = int(rng.integers(lo_w, bw + 1))
        images.append(rng.normal(size=(h, w, ci)).astype(np.float32))
    return images


def run_load(server, images, rng, burst: int):
    """Feed the trace in bursts between engine steps — the continuous part
    of continuous batching: admission happens while earlier batches run,
    so slots refill from the queue and buckets execute partially full."""
    from repro.serve import ConvRequest
    i = 0
    while i < len(images) or server.pool.pending:
        k = int(rng.integers(1, 2 * burst)) if i < len(images) else 0
        for img in images[i:i + k]:
            server.submit(ConvRequest(rid=i, image=img))
            i += 1
        server.step()
    return server.completed


def run_chaos_load(server, images, rng, burst: int, deadline_every: int):
    """The chaos variant of :func:`run_load`: same burst admission, but
    every ``deadline_every``-th request is submitted with an already-expired
    deadline (``timeout=-1``) — it deterministically sweeps out TIMED_OUT on
    the next step, independent of machine speed."""
    from repro.serve import ConvRequest
    i = 0
    while i < len(images) or server.pool.pending:
        k = int(rng.integers(1, 2 * burst)) if i < len(images) else 0
        for img in images[i:i + k]:
            timeout = -1.0 if i % deadline_every == deadline_every - 1 \
                else None
            server.submit(ConvRequest(rid=i, image=img), timeout=timeout)
            i += 1
        server.step()
    return server.completed


def faults_rows(server, n_requests: int, dtype_name: str = "f32"):
    """-> the one ``faults`` gate row: degraded-mode latency + the
    deterministic outcome counters.  ``*_count``/``*_rate`` fields gate
    exactly (the chaos trace is bit-stable); ``*_us`` fields gate like any
    other timing."""
    import numpy as np
    h = server.health()
    # the acceptance invariant: every submission terminated in the lattice
    assert h["ok"] + h["shed"] + h["timed_out"] == n_requests, h
    assert h["pending"] == 0, h
    lat = server.latencies() * 1e6
    return [{
        "layer": "serve.chaos",
        "dtype": dtype_name,
        "p50_us": float(np.percentile(lat, 50)),
        "p99_us": float(np.percentile(lat, 99)),
        "completed": h["ok"],
        "shed_count": h["shed"],
        "timed_out_count": h["timed_out"],
        "retry_count": h["retries"],
        "transient_fault_count": h["transient_faults"],
        "degraded_step_count": h["degraded_steps"],
        "admit_fault_count": h["admit_faults"],
        "shed_rate": h["shed_rate"],
        "steps": h["steps"],
        "breakers": h["breakers"],
    }]


def serve_rows(server, dtype_name: str = "f32"):
    """-> one gate row per bucket: p50/p99 latency (us) + occupancy."""
    import numpy as np
    rows = []
    for bucket in server.bucketer.buckets:
        lat = server.latencies(bucket) * 1e6
        if not len(lat):
            continue
        rows.append({
            "layer": f"serve.{bucket[0]}x{bucket[1]}",
            "dtype": dtype_name,
            "p50_us": float(np.percentile(lat, 50)),
            "p99_us": float(np.percentile(lat, 99)),
            "occupancy": server.occupancy(bucket),
            "requests": int(len(lat)),
        })
    return rows


def shard_dispatch_rows(model, mesh, buckets, batch: int, axis: str,
                        model_axis, dtype_name: str = "f32"):
    """The routing record for the serve rows: per-shard dispatch keys.

    One row per (bucket, conv layer, direction): the key each shard
    resolves at trace time — batch over the data width, Co over the model
    width (``DispatchKey.shard``) — with the impl and source the process
    dispatcher picks for it.  Rows are keyed by the bucket's serve layer
    name so ``check_regression``'s coverage pass links them to the gate
    rows; per-conv detail rides in the ``conv`` field.
    """
    from repro.core.blocking import TPU_V5E
    from repro.core.dispatch import DispatchKey, get_dispatcher
    disp = get_dispatcher()
    data = mesh.shape[axis]
    m = mesh.shape[model_axis] if model_axis is not None else 1
    rows = []
    for bh, bw in buckets:
        hi, wi = bh, bw
        for i, conv in enumerate(model.convs):
            lay = conv.layout
            for direction in ("fwd",):      # serving is inference-only
                key = DispatchKey.make(
                    batch, hi, wi, conv.ci, conv.co, conv.hf, conv.wf,
                    conv.stride, conv.padding, dtype_name, TPU_V5E,
                    direction, groups=conv.groups, dilation=conv.dilation
                ).shard(data=data, model=m)
                dec = disp.decide(key, cob=lay.cb_out, cib=lay.cb_in)
                rows.append({
                    "layer": f"serve.{bh}x{bw}", "conv": f"conv{i}",
                    "dtype": dtype_name, "machine": TPU_V5E.name,
                    "direction": direction, "shards": f"{data}x{m}",
                    "impl": dec.impl.value, "source": dec.source,
                    "key": key.ident,
                })
            hi, wi = key.spec.ho, key.spec.wo     # next layer's input extent
    return rows


def merge_report(path: str, sections: dict, dispatch=None):
    """Write this bench's sections into ``path``, merging with an existing
    report (the CI job appends to fig_conv's file): each named section
    (``serve``, ``faults``) replaces its previous value; serve ``dispatch``
    rows append (fig_conv's own rows are keyed by different layers, so the
    union is disjoint)."""
    report = {}
    if os.path.exists(path):
        with open(path) as f:
            report = json.load(f)
    report.update(sections)
    if dispatch is not None:
        existing = [r for r in report.get("dispatch", [])
                    if not r.get("layer", "").startswith("serve.")]
        report["dispatch"] = existing + dispatch
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {path}")


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    import numpy as np
    from repro.launch.conv_serve import ConvServer
    from repro.launch.mesh import make_serve_mesh
    from repro.nn.module import init_tree
    from repro.utils.cache import enable_compile_cache

    enable_compile_cache()
    model = build_smoke_model()
    m = max(args.model_shard, 1)
    # the mesh covers the visible devices (virtual CPU devices come from
    # XLA_FLAGS=--xla_force_host_platform_device_count, set by the caller)
    mesh = make_serve_mesh(model=m)
    data = mesh.shape["data"]
    batch = -(-args.batch // data) * data
    model_axis = "model" if m > 1 else None

    params = init_tree(model.specs(), jax.random.PRNGKey(0))

    if args.faults:
        from repro.core.errors import KernelLaunchError, TransientError
        from repro.utils.faults import FaultPlan, FaultRule, fault_plan
        server = ConvServer(model, params, mesh, CI_BUCKETS, batch,
                            model_axis=model_axis, clock=time.monotonic,
                            max_queue=args.max_queue, max_retries=2,
                            backoff=0.0)
        server.warmup()               # compiles outside the armed plan
        plan = FaultPlan((
            FaultRule(site="serve.step", error=KernelLaunchError,
                      rate=args.fault_rate),
            FaultRule(site="slots.admit", error=TransientError, rate=0.05),
        ), seed=args.seed)
        rng = np.random.default_rng(args.seed)
        images = synth_trace(rng, args.requests, CI_BUCKETS,
                             ci=model.convs[0].ci)
        with fault_plan(plan):
            run_chaos_load(server, images, rng, args.burst,
                           args.deadline_every)
        faults = faults_rows(server, args.requests)
        print(f"== faults ==  mesh={dict(mesh.shape)} batch={batch} "
              f"rate={args.fault_rate} seed={args.seed}")
        for row in faults:
            print("  " + " ".join(
                f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in row.items()))
        if args.json:
            merge_report(args.json, {"faults": faults})
        return 0

    server = ConvServer(model, params, mesh, CI_BUCKETS, batch,
                        model_axis=model_axis, clock=time.monotonic)
    server.warmup()

    rng = np.random.default_rng(args.seed)
    images = synth_trace(rng, args.requests, CI_BUCKETS,
                         ci=model.convs[0].ci)
    done = run_load(server, images, rng, args.burst)
    assert len(done) == args.requests, (len(done), args.requests)

    serve = serve_rows(server)
    dispatch = shard_dispatch_rows(model, mesh, CI_BUCKETS, batch,
                                   server.axis, model_axis)
    print(f"== serve ==  mesh={dict(mesh.shape)} batch={batch}")
    for row in serve:
        print("  " + " ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in row.items()))
    for row in dispatch:
        print("  " + " ".join(f"{k}={v}" for k, v in row.items()))
    if args.json:
        merge_report(args.json, {"serve": serve}, dispatch)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
