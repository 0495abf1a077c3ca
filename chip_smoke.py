#!/usr/bin/env python3
"""Smoke test on the chip: MobileNet-v1 1.0-224 served and trained through
the blocked direct-conv Pallas kernels.

    python chip_smoke.py              # one chip: serve + reference + train
    python chip_smoke.py --chips 4    # four chips: the sharded serving mesh

One chip runs these phases in one process:

  device     platform, device_kind and device count; no TPU -> exit 2
  table      the checked-in dispatch table loads with no warning
  routing    every conv's dispatch decision (impl, source), all Pallas;
             the compiled forward holds ``tpu_custom_call``s
  serve      ``ConvServer`` over ``make_serve_mesh()``: two buckets, batch
             8, warmup (reported as compile time), 24 seeded requests of
             sizes up to 224x224; every outcome OK, no degraded step, no
             transient fault
  reference  served logits against a float32 ``lax.conv_general_dilated``
             network at ``precision=HIGHEST``
  train      3 steps of ``make_train_step`` at batch 8 through the Pallas
             custom VJPs under the f32 and the bf16 policy; finite losses,
             the first one against the jnp path

``--chips 4`` runs only the sharded path: MobileNet behind ``ConvServer``
on a data=4 mesh, and a dense 128-multiple-wide stack through
``make_sharded_cnn_forward(model_axis="model")`` on data=2 x model=2, each
against single-device logits.

Weights are random from ``--seed``.  Wall times go to earlier lines; the
last line is one JSON object naming the device.  A failed phase raises, so
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_BUCKETS = ((160, 160), (224, 224))
BATCH = 8
N_REQUESTS = 24
TRAIN_STEPS = 3
# first-loss agreement with the jnp path, relative: f32 formulations agree
# to float32 rounding over 27 layers; bf16 ones each round operands and
# outputs to 8 mantissa bits (eps ~ 4e-3), compounded over the layers
LOSS_TOL = {"f32": 1e-3, "bf16": 5e-2}
# served logits against the f32 HIGHEST lax network, relative to the
# largest reference logit
REF_TOL = 1e-2
# sharded against single-device logits, relative (the per-shard program is
# the single-device program; expected bit-identical)
SHARD_TOL = 1e-5


class Timer:
    """Wall time per phase, printed as it ends."""

    def __init__(self):
        self.times = {}

    def __call__(self, name):
        timer = self

        class _Span:
            def __enter__(self):
                self.t = time.perf_counter()

            def __exit__(self, *exc):
                if exc[0] is None:
                    dt = time.perf_counter() - self.t
                    timer.times[name] = dt
                    print(f"[time] {name}: {dt:.3f} s", flush=True)
        return _Span()


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# building blocks (importable: a CPU rehearsal drives them at a small size)
# ---------------------------------------------------------------------------

def init_params(model, seed):
    """Random weights from ``seed``, He-scaled for the ReLU stack so the
    activations of all 27 layers stay O(1)."""
    import jax
    from repro.nn.module import init_tree
    p = init_tree(model.specs(), jax.random.PRNGKey(seed))

    def he(path, x):
        name = jax.tree_util.keystr(path)
        return x * math.sqrt(2.0) if name.endswith("['w']") else x
    return jax.tree_util.tree_map_with_path(he, p)


def load_table():
    """The checked-in dispatch table, loaded strictly: a table that fails
    to load would quietly route by the prior, so here it is an error."""
    from repro.core.dispatch import ConvDispatcher
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        return ConvDispatcher.from_file(missing_ok=False)


def conv_layers(model):
    """The model's leaf convs in order, with their input spatial extent
    at a ``size`` x ``size`` image: -> [(name, BlockedConv2D, gap)]."""
    from repro.nn.conv import DepthwiseSeparableBlock
    leaves = []
    for i, c in enumerate(model.convs):
        last = i == len(model.convs) - 1
        if isinstance(c, DepthwiseSeparableBlock):
            leaves.append((f"conv{i}.dw", c.depthwise, False))
            leaves.append((f"conv{i}.pw", c.pointwise, last))
        else:
            leaves.append((f"conv{i}", c, last))
    return leaves


def routing(model, disp, size, batch, precision):
    """Each conv's forward dispatch decision; -> [(name, impl, source)]."""
    from repro.core.backend import resolve_machine
    from repro.core.dispatch import DispatchKey
    from repro.core.precision import resolve_precision
    pol = resolve_precision(precision)
    machine = resolve_machine(None)
    h = size
    out = []
    for name, conv, gap in conv_layers(model):
        toks = [t for t, on in (("gap", gap), ("dz", conv.activation not in
                                               (None, "linear"))) if on]
        key = DispatchKey.make(batch, h, h, conv.ci, conv.co, conv.hf,
                               conv.wf, conv.stride, conv.padding, pol,
                               machine, "fwd", groups=conv.groups,
                               dilation=conv.dilation, fusion="+".join(toks))
        lay = conv.layout
        dec = disp.decide(key, cob=lay.cb_out, cib=lay.cb_in)
        out.append((name, dec.impl.value, dec.source))
        h = key.spec.ho
    return out


def reference_logits(model, params, x_nhwc):
    """The same network in NHWC through ``lax.conv_general_dilated`` at
    float32 ``precision=HIGHEST`` (weights unblocked with
    ``blocked_to_hwio``)."""
    import jax
    import jax.numpy as jnp
    from repro.core.layout import blocked_to_hwio

    def conv(p, c, h):
        w = blocked_to_hwio(p["w"]).astype(jnp.float32)
        y = jax.lax.conv_general_dilated(
            h, w, (c.stride, c.stride), c.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c.groups,
            precision=jax.lax.Precision.HIGHEST)
        if c.use_bias:
            y = y + p["b"].reshape(-1)
        return jax.nn.relu(y)

    def fwd(params, x):
        h = x.astype(jnp.float32)
        for i, c in enumerate(model.convs):
            p = params[f"conv{i}"]
            if hasattr(c, "depthwise"):
                h = conv(p["dw"], c.depthwise, h)
                h = conv(p["pw"], c.pointwise, h)
            else:
                h = conv(p, c, h)
        feat = h.mean(axis=(1, 2))
        return jnp.dot(feat, params["head"],
                       precision=jax.lax.Precision.HIGHEST)
    return jax.jit(fwd)(params, x_nhwc)


def requests(seed, n, max_size, channels):
    """Seeded requests whose square sizes spread up to ``max_size``."""
    import numpy as np
    from repro.serve.scheduler import ConvRequest
    rng = np.random.default_rng(seed)
    sizes = rng.integers(max_size // 2, max_size + 1, n)
    return [ConvRequest(rid=i, image=rng.normal(
        size=(int(s), int(s), channels)).astype(np.float32))
        for i, s in enumerate(sizes)]


def serve(model, params, mesh, ctx, buckets, batch, reqs, timer,
          tag="serve"):
    """Run ``reqs`` through a ``ConvServer``; -> (server, completed)."""
    from repro.launch.conv_serve import ConvServer
    from repro.serve.scheduler import Outcome
    server = ConvServer(model, params, mesh, buckets, batch, context=ctx)
    with timer(f"{tag}.compile (warmup)"):
        server.warmup()
    with timer(f"{tag}.run ({len(reqs)} requests)"):
        for r in reqs:
            server.submit(r)
        done = server.run()
    health = server.health()
    print(f"[{tag}] outcomes: {sorted(set(r.outcome.value for r in done))}"
          f"  degraded_steps={health['degraded_steps']}"
          f"  transient_faults={health['transient_faults']}"
          f"  steps={health['steps']}", flush=True)
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} completed")
    check(all(r.outcome is Outcome.OK for r in done), "a request not OK")
    check(health["degraded_steps"] == 0, "a step degraded to the jnp path")
    check(health["transient_faults"] == 0, "a transient fault was caught")
    return server, done


def padded_batch(server, reqs):
    """The requests as the server ran them: padded to their bucket."""
    import numpy as np
    return np.stack([server.bucketer.pad(r.image, r.bucket) for r in reqs])


def rel_err(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def train(model, params, ctx_for, size, batch, steps, timer, seed,
          policies=("f32", "bf16")):
    """``steps`` of ``make_train_step`` per policy through the Pallas
    routes; the first loss is checked against the jnp path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.train.optimizer import AdamW, cosine_schedule
    from repro.train.trainstep import (TrainSettings, make_loss_fn,
                                       make_train_step)
    rng = np.random.default_rng(seed)
    batch_d = {"images": jnp.asarray(rng.normal(
        size=(batch, size, size, model.convs[0].ci)).astype(np.float32)),
        "targets": jnp.asarray(rng.integers(0, model.n_classes, batch))}
    opt = AdamW(lr=cosine_schedule(1e-3, 1, steps), weight_decay=0.0)
    for pol in policies:
        settings = TrainSettings(context=ctx_for(pol))
        step = jax.jit(make_train_step(model, None, opt, settings))
        p, st = params, opt.init(params)
        with timer(f"train.{pol}.compile"):
            step = step.lower(p, st, batch_d).compile()
        losses = []
        with timer(f"train.{pol}.run ({steps} steps)"):
            for _ in range(steps):
                p, st, m = step(p, st, batch_d)
                losses.append(float(m["nll"]))
        print(f"[train.{pol}] losses: {losses}", flush=True)
        check(all(math.isfinite(v) for v in losses),
              f"non-finite loss under {pol}")
        jnp_settings = TrainSettings(
            context=ctx_for(pol).override(impl="jnp"))
        ref_loss, _ = jax.jit(make_loss_fn(model, None, jnp_settings))(
            params, batch_d)
        ref_loss = float(ref_loss)
        tol = LOSS_TOL[pol]
        err = abs(losses[0] - ref_loss) / max(1.0, abs(ref_loss))
        print(f"[train.{pol}] first loss {losses[0]:.6f} vs jnp "
              f"{ref_loss:.6f}: rel err {err:.3e} (tol {tol:g})", flush=True)
        check(err <= tol, f"{pol} first loss disagrees with the jnp path")


def hlo_has_kernels(fn, *args):
    """The compiled program's ``tpu_custom_call`` count."""
    import jax
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def one_chip(args, timer):
    import numpy as np
    from repro.configs.mobilenet import INPUT_SIZE, mobilenet_v1
    from repro.core.context import ConvContext
    from repro.core.dispatch import PALLAS_FAMILY, Impl
    from repro.launch.mesh import make_serve_mesh

    with timer("table"):
        disp = load_table()
    model = mobilenet_v1()
    params = init_params(model, args.seed)
    ctx = ConvContext(dispatch=disp)

    with timer("routing"):
        routes = routing(model, disp, INPUT_SIZE, BATCH, "f32")
        for name, impl, source in routes:
            print(f"[route] {name}: {impl} ({source})")
        bad = [r for r in routes if Impl(r[1]) not in PALLAS_FAMILY]
        check(not bad, f"convs not routed to a Pallas impl: {bad}")
        x0 = np.zeros((BATCH, INPUT_SIZE, INPUT_SIZE, 3), np.float32)
        n_calls = hlo_has_kernels(
            lambda p, x: model(p, x, context=ctx), params, x0)
        print(f"[route] compiled forward: {n_calls} tpu_custom_call")
        check(n_calls >= len(routes), "the forward lost its Pallas kernels")

    reqs = requests(args.seed, N_REQUESTS, INPUT_SIZE, 3)
    server, done = serve(model, params, make_serve_mesh(), ctx,
                         SERVE_BUCKETS, BATCH, reqs, timer)

    with timer("reference"):
        pick = [r for r in done if r.bucket == SERVE_BUCKETS[-1]][:4]
        want = reference_logits(model, params, padded_batch(server, pick))
        got = np.stack([r.logits for r in pick])
        err = rel_err(got, want)
        print(f"[reference] served vs lax f32 HIGHEST: max abs err "
              f"{err * float(np.max(np.abs(want))):.3e}, relative {err:.3e}"
              f" (tol {REF_TOL:g})", flush=True)
        check(err <= REF_TOL, "served logits disagree with the reference")

    train(model, params, lambda pol: ConvContext(dispatch=disp,
                                                 precision=pol),
          INPUT_SIZE, BATCH, TRAIN_STEPS, timer, args.seed)


def four_chips(args, timer):
    import jax
    import numpy as np
    from repro.configs.mobilenet import INPUT_SIZE, mobilenet_v1
    from repro.core.context import ConvContext
    from repro.launch.conv_serve import make_sharded_cnn_forward
    from repro.launch.mesh import make_serve_mesh
    from repro.nn.conv import BlockedCNN, BlockedConv2D

    disp = load_table()
    ctx = ConvContext(dispatch=disp)

    # MobileNet behind the server on data=4, against one device
    model = mobilenet_v1()
    params = init_params(model, args.seed)
    mesh = make_serve_mesh()
    print(f"[mesh] serve: {dict(mesh.shape)}")
    check(mesh.shape["data"] == 4, "the serving mesh is not data=4")
    reqs = requests(args.seed, N_REQUESTS, INPUT_SIZE, 3)
    server, done = serve(model, params, mesh, ctx, SERVE_BUCKETS, BATCH,
                         reqs, timer, tag="serve.data4")
    with timer("serve.data4.single_device"):
        one = jax.jit(lambda p, x: model(p, x, context=ctx))
        worst = 0.0
        for bucket in SERVE_BUCKETS:
            sel = [r for r in done if r.bucket == bucket][:BATCH]
            if not sel:
                continue
            x = padded_batch(server, sel)
            want = one(params, np.concatenate(
                [x, np.zeros((BATCH - len(sel),) + x.shape[1:], x.dtype)]))
            got = np.stack([r.logits for r in sel])
            worst = max(worst, rel_err(got, np.asarray(want)[:len(sel)]))
        print(f"[serve.data4] vs single device: relative err {worst:.3e} "
              f"(tol {SHARD_TOL:g})", flush=True)
        check(worst <= SHARD_TOL, "data=4 logits disagree with one device")

    # a dense stack Co-sharded on data=2 x model=2, against one device
    dense = BlockedCNN(convs=(
        BlockedConv2D(ci=3, co=256, stride=2),
        BlockedConv2D(ci=256, co=256),
        BlockedConv2D(ci=256, co=512, stride=2),
        BlockedConv2D(ci=512, co=512)), n_classes=1000)
    dparams = init_params(dense, args.seed + 1)
    mesh2 = make_serve_mesh(model=2)
    print(f"[mesh] co-sharded: {dict(mesh2.shape)}")
    x = np.random.default_rng(args.seed).normal(
        size=(BATCH, 112, 112, 3)).astype(np.float32)
    with timer("dense.data2xmodel2"):
        f = make_sharded_cnn_forward(dense, mesh2, "data",
                                     model_axis="model", context=ctx)
        got = np.asarray(jax.block_until_ready(f(dparams, x)))
    with timer("dense.single_device"):
        want = np.asarray(jax.jit(
            lambda p, x: dense(p, x, context=ctx))(dparams, x))
    err = rel_err(got, want)
    print(f"[dense.data2xmodel2] vs single device: relative err {err:.3e} "
          f"(tol {SHARD_TOL:g})", flush=True)
    check(err <= SHARD_TOL, "co-sharded logits disagree with one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    print(f"[device] platform={platform} device_kind={kind!r} "
          f"count={count}", flush=True)
    if platform != "tpu":
        print("chip_smoke: no TPU found; this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {count}", file=sys.stderr)
        return 2

    from repro.utils.cache import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}", flush=True)
    timer = Timer()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, timer)
    else:
        one_chip(args, timer)
    print(f"[time] total: {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
