"""Paper Fig. 1 + Fig. 4: direct convolution vs im2col+GEMM vs FFT across the
CNN-layer zoo, plus the packing-overhead split (im2col time vs GEMM time).

Caveat (documented in EXPERIMENTS.md): the container CPU executes XLA's CPU
backend for every algorithm, so absolute numbers are not the paper's
hand-tuned SIMD kernels; what reproduces is the *structure* — packing costs
real time (Fig. 1), direct avoids it entirely with identical math, FFT's
competitiveness depends on kernel size (Fig. 4).  Memory overheads (the
headline claim) are exact, from compiled buffer analysis in memory_table.py.

Runnable:  PYTHONPATH=src python -m benchmarks.fig_conv [--backward] [--json f]
(the ``-m`` form is required — the module uses relative imports).
``--backward`` adds fwd+bwd training-step timings; ``--smoke`` uses the
pinned CI-sized shapes (``CI_SHAPES`` — the CI bench job's fixed set, so the
``BENCH_*.json`` trajectory is comparable run to run); ``--dtype f32
--dtype bf16`` sweeps the mixed-precision operand dtype (rows are tagged,
accumulation stays f32 per the precision policy); ``--stream`` adds the
streamed halo-DMA kernel section (DESIGN.md §11): fwd + fwd+bwd step
timings through ``stream=True`` for the CI shapes AND a "pathological"
deep-pinned-pencil shape on a tiny ``MachineModel`` — the configuration
that hard-raised before ISSUE 5 — plus the per-shape halo-traffic delta
(``memory_model.bytes_halo_refetch``, window tiles vs streamed bands).
The ``fusion`` section (always on, DESIGN.md §14) times the fused
epilogue/prologue against its two-pass reference on the ``smoke.res``/
``smoke.gap`` shapes and carries the HBM bytes fusion saves
(``memory_model.bytes_epilogue_fusion``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import conv_baselines as B
from repro.core import direct_conv as D
from repro.core import layout as LAY
from repro.core.blocking import (Blocking, MachineModel, TPU_V5E,
                                 VmemMisfitError, choose_blocking,
                                 choose_stream_blocking)
from repro.core.memory_model import (ConvShape, bytes_epilogue_fusion,
                                     bytes_halo_refetch)
from repro.kernels.direct_conv2d import direct_conv2d_blocked_pallas

from .cnn_zoo import ZOO, ALEXNET
from .timing import resolve_bench_dtype, time_fn

# The CI bench job's pinned shape set: small enough for a CPU runner, big
# enough to cross tile boundaries.  Changing these invalidates the
# checked-in BENCH_baseline.json — regenerate it in the same PR.
CI_SHAPES = [
    ConvShape("smoke.3x3", 1, 12, 12, 4, 8, 3, 3, pad=1),
    ConvShape("smoke.s2", 1, 12, 12, 8, 8, 3, 3, stride=2, pad="SAME"),
    # the kernel zoo (DESIGN.md §13): depthwise, block-diagonal grouped,
    # and the 1x1-as-matmul fast path — each routes to its specialized impl
    ConvShape("smoke.dw", 1, 12, 12, 8, 8, 3, 3, pad=1, groups=8),
    ConvShape("smoke.grp", 1, 12, 12, 8, 8, 3, 3, pad=1, groups=2),
    ConvShape("smoke.1x1", 1, 12, 12, 8, 16, 1, 1),
    # the fused-epilogue rows (DESIGN.md §14): smoke.res is identity-shaped
    # (ci == co, stride 1, SAME) so the residual-add fuses a skip tensor of
    # the output geometry; smoke.gap drains its epilogue into the fused
    # global-average-pool partial sums
    ConvShape("smoke.res", 1, 12, 12, 8, 8, 3, 3, pad=1),
    ConvShape("smoke.gap", 1, 12, 12, 8, 16, 3, 3, pad=1),
]

# The fused-vs-unfused section's shapes: the two fusion smoke rows above.
FUSION_SHAPES = [s for s in CI_SHAPES if s.name in ("smoke.res",
                                                    "smoke.gap")]

# The streamed section's machine for the pathological rows: pinned 32-deep
# pencils against a 50 KB budget misfit the window inequality even at
# hob = wob = 1 (the pre-ISSUE-5 hard raise) while the streamed floor fits.
STREAM_TINY = MachineModel(name="ci-deep-pencil", n_vec=32, n_fma=1,
                           l_fma=8, n_reg=64, vmem_bytes=50_000)

# (shape, machine) pairs the --stream section times: the pinned CI shapes on
# the default model (streamed forced, for a like-for-like trajectory against
# the window rows) and the previously-fatal deep pencil on STREAM_TINY
# (streamed is the ONLY path that runs).  Same baseline-invalidated-on-change
# contract as CI_SHAPES.
STREAM_SHAPES = [
    (CI_SHAPES[0], TPU_V5E),
    (CI_SHAPES[1], TPU_V5E),
    (ConvShape("patho.pencil32", 1, 6, 6, 32, 32, 3, 3, pad=1), STREAM_TINY),
]


def _inputs(s: ConvShape, dtype=jnp.float32):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(s.n, s.hi, s.wi, s.ci)), dtype)
    # grouped weights carry the per-group input extent (HWIO with
    # w.shape[2] == Ci // groups — the lax feature_group_count convention
    # every consumer here shares)
    w = jnp.asarray(rng.normal(size=(s.hf, s.wf, s.cig, s.co)), dtype)
    return x, w


def bench_fig4(shapes=None, iters=3):
    """-> rows: per-layer seconds for direct / im2col+GEMM / FFT / lax.

    im2col and FFT are dense-only formulations (packing a block-diagonal
    weight would benchmark a different algorithm), so grouped/depthwise
    rows omit those columns — the regression gate keys per-field and
    simply has no im2col/fft trajectory for them.
    """
    rows = []
    for s in shapes or ZOO:
        x, w = _inputs(s)
        pad = s.pad
        t_direct = time_fn(
            lambda x, w: D.direct_conv_nhwc(x, w, s.stride, pad,
                                            groups=s.groups,
                                            dilation=s.dilation),
            x, w, iters=iters)
        t_lax = time_fn(
            lambda x, w: B.conv_lax(x, w, s.stride, pad, groups=s.groups,
                                    dilation=s.dilation),
            x, w, iters=iters)
        # unrounded: the CI shapes are ~1e-4 GFLOP, which round(_, 3) used
        # to flatten to 0.0 while direct_gflops was computed from the real
        # value — the two fields must agree (gflop == direct_gflops * t)
        gf = s.flops() / 1e9
        row = {
            "layer": s.name, "gflop": gf,
            "direct_us": t_direct * 1e6, "lax_us": t_lax * 1e6,
            "direct_gflops": gf / t_direct,
        }
        if s.groups == 1 and s.dil == (1, 1):
            t_im2col = time_fn(
                lambda x, w: B.conv_im2col(x, w, s.stride, pad),
                x, w, iters=iters)
            t_fft = time_fn(lambda x, w: B.conv_fft(x, w, s.stride, pad),
                            x, w, iters=iters)
            row["im2col_us"] = t_im2col * 1e6
            row["fft_us"] = t_fft * 1e6
            row["direct_vs_im2col"] = t_im2col / t_direct
        rows.append(row)
    return rows


def bench_backward(shapes=None, iters=3, dtype_name="f32"):
    """fwd vs fwd+bwd step timings for the direct path and the XLA oracle.

    The backward of the direct formulation is itself a direct convolution
    (transposed-window dgrad + per-tile wgrad — DESIGN.md §9), so the
    fwd+bwd/fwd ratio should track the oracle's: one step is ~3 convs.
    Rows land in the benchmark JSON via ``--backward --json``.

    ``dtype_name`` is the precision policy's operand dtype ("f32"/"bf16"):
    inputs are cast once by ``time_fn``, accumulation stays f32 inside the
    direct path (the policy's guarantee), and every row carries its dtype so
    the CI regression gate keys on (layer, dtype).
    """
    dtype = resolve_bench_dtype(dtype_name)
    rows = []
    for s in shapes or ZOO:
        x, w = _inputs(s)
        pad = s.pad

        def direct_fn(x, w):
            return D.direct_conv_nhwc(x, w, s.stride, pad, groups=s.groups,
                                      dilation=s.dilation)

        def lax_fn(x, w):
            return B.conv_lax(x, w, s.stride, pad, groups=s.groups,
                              dilation=s.dilation)

        t_fwd = time_fn(direct_fn, x, w, iters=iters, dtype=dtype)
        t_step = time_fn(direct_fn, x, w, iters=iters, backward=True,
                         dtype=dtype)
        t_lax_fwd = time_fn(lax_fn, x, w, iters=iters, dtype=dtype)
        t_lax_step = time_fn(lax_fn, x, w, iters=iters, backward=True,
                             dtype=dtype)
        rows.append({
            "layer": s.name,
            "dtype": dtype_name,
            "direct_fwd_us": t_fwd * 1e6,
            "direct_fwdbwd_us": t_step * 1e6,
            "lax_fwd_us": t_lax_fwd * 1e6,
            "lax_fwdbwd_us": t_lax_step * 1e6,
            "direct_bwd_over_fwd": t_step / max(t_fwd, 1e-12),
            "direct_vs_lax_step": t_step / max(t_lax_step, 1e-12),
        })
    return rows


def _blocked_operands(s: ConvShape, lane: int = 128):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(s.n, s.hi, s.wi, s.ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(s.hf, s.wf, s.cig, s.co)), jnp.float32)
    lay = LAY.BlockedConvLayout.choose(s.ci, s.co, lane=lane,
                                       groups=s.groups)
    return (LAY.nhwc_to_blocked(x, lay.cb_in),
            LAY.hwio_to_blocked(w, lay.cb_weight, lay.cb_out), lay)


def _halo_bytes(s: ConvShape, machine, lay, dtype_name: str):
    """(window, streamed) re-fetch bytes under each path's chosen blocking.

    When the window inequality misfits outright (the pathological rows) the
    window number is the ``hob = wob = 1`` floor it was driving toward —
    the traffic it would have paid had it been allowed to launch."""
    kw = dict(machine=machine, cob=lay.cb_out, cib=lay.cb_in,
              precision=dtype_name)
    try:
        wblk = choose_blocking(s.padded_hi, s.padded_wi, s.ci, s.co,
                               s.hf, s.wf, s.stride, **kw)
    except VmemMisfitError:
        wblk = Blocking(cob=lay.cb_out, cib=lay.cb_in, hob=1, wob=1)
    sblk = choose_stream_blocking(s.padded_hi, s.padded_wi, s.ci, s.co,
                                  s.hf, s.wf, s.stride, **kw)
    dtype_bytes = resolve_bench_dtype(dtype_name).itemsize
    return (bytes_halo_refetch(s, wblk, dtype_bytes),
            bytes_halo_refetch(s, sblk, dtype_bytes))


def bench_stream(shapes=None, iters=3, dtype_name="f32"):
    """The streamed halo-DMA kernel section (``--stream``, DESIGN.md §11).

    Per (shape, machine) pair: fwd and fwd+bwd step times through
    ``direct_conv2d_blocked_pallas(stream=True)`` (interpret mode off the TPU —
    the trajectory tracks relative drift, not TPU wall-clock), the window
    path's fwd time when its inequality fits (absent for the pathological
    rows: that path *raises* there, which is the point), and the
    halo-traffic delta between the two paths' chosen blockings.  Only the
    ``*_us`` fields gate in CI; the byte columns are the accounting.
    """
    dtype = resolve_bench_dtype(dtype_name)
    rows = []
    for s, machine in shapes or STREAM_SHAPES:
        xb, wb, lay = _blocked_operands(s)

        def stream_fn(xb_, wb_):
            return direct_conv2d_blocked_pallas(
                xb_, wb_, stride=s.stride, padding=s.pad, machine=machine,
                precision=dtype_name, stream=True)

        t_fwd = time_fn(stream_fn, xb, wb, iters=iters, dtype=dtype)
        t_step = time_fn(stream_fn, xb, wb, iters=iters, backward=True,
                         dtype=dtype)
        halo_window, halo_stream = _halo_bytes(s, machine, lay, dtype_name)
        row = {
            "layer": s.name,
            "dtype": dtype_name,
            "machine": machine.name,
            "stream_fwd_us": t_fwd * 1e6,
            "stream_fwdbwd_us": t_step * 1e6,
            "halo_window_bytes": halo_window,
            "halo_stream_bytes": halo_stream,
            "halo_saved_bytes": halo_window - halo_stream,
        }
        try:
            def window_fn(xb_, wb_):
                return direct_conv2d_blocked_pallas(
                    xb_, wb_, stride=s.stride, padding=s.pad,
                    machine=machine, precision=dtype_name, stream=False)
            row["window_fwd_us"] = time_fn(window_fn, xb, wb, iters=iters,
                                           dtype=dtype) * 1e6
        except VmemMisfitError:
            pass          # the pathological rows: streamed is the only path
        rows.append(row)
    return rows


def bench_fusion(shapes=None, iters=3, dtype_name="f32"):
    """Fused vs unfused epilogue step timings + the HBM bytes fusion saves.

    One row per fusion smoke shape: ``smoke.res`` fuses the residual add
    into the epilogue (vs. conv-then-add), ``smoke.gap`` fuses global
    average pooling (vs. conv-then-pool).  Both fwd and fwd+bwd steps are
    timed — the backward of the fused path forms ``dz = g * act'(z)`` on
    tile load inside dgrad/wgrad (the prologue fusion) where the unfused
    reference materializes dz between kernels.  Interpret-mode on CPU, so
    the ``*_us`` trajectory tracks relative drift only; the authoritative
    fused-vs-unfused comparison is ``fusion_saved_bytes``
    (``memory_model.bytes_epilogue_fusion`` — the HBM round-trips the fused
    epilogue/prologue provably removes), which must be > 0 for every row.
    """
    dtype = resolve_bench_dtype(dtype_name)
    dtype_bytes = dtype.itemsize
    rows = []
    for s in shapes or FUSION_SHAPES:
        xb, wb, lay = _blocked_operands(s)
        gap = s.name.endswith(".gap")
        rng = np.random.default_rng(1)
        res = None if gap else jnp.asarray(
            rng.normal(size=(s.n, s.co // lay.cb_out, s.ho, s.wo,
                             lay.cb_out)), jnp.float32)

        kw = dict(stride=s.stride, padding=s.pad, activation="relu",
                  precision=dtype_name)

        if gap:
            def fused_fn(xb_, wb_):
                return direct_conv2d_blocked_pallas(xb_, wb_, gap=True, **kw)

            def unfused_fn(xb_, wb_):
                y = direct_conv2d_blocked_pallas(xb_, wb_, **kw)
                n, cblk, _, _, cb = y.shape
                pooled = jnp.mean(y.astype(jnp.float32), axis=(2, 3))
                return pooled.reshape(n, cblk * cb).astype(y.dtype)

            args = (xb, wb)
        else:
            def fused_fn(xb_, wb_, r_):
                return direct_conv2d_blocked_pallas(xb_, wb_, residual=r_,
                                                    **kw)

            def unfused_fn(xb_, wb_, r_):
                y = direct_conv2d_blocked_pallas(xb_, wb_, **kw)
                return (y.astype(jnp.float32)
                        + r_.astype(jnp.float32)).astype(y.dtype)

            args = (xb, wb, res)

        row = {
            "layer": s.name, "dtype": dtype_name,
            "fused_fwd_us": time_fn(fused_fn, *args, iters=iters,
                                    dtype=dtype) * 1e6,
            "unfused_fwd_us": time_fn(unfused_fn, *args, iters=iters,
                                      dtype=dtype) * 1e6,
            "fused_fwdbwd_us": time_fn(fused_fn, *args, iters=iters,
                                       backward=True, dtype=dtype) * 1e6,
            "unfused_fwdbwd_us": time_fn(unfused_fn, *args, iters=iters,
                                         backward=True, dtype=dtype) * 1e6,
            "fusion_saved_bytes": bytes_epilogue_fusion(
                s, dtype_bytes, residual=not gap, gap=gap, act_bwd=True),
        }
        rows.append(row)
    return rows


def dispatch_report(pairs=None, dtypes=("f32",)):
    """Which impl the dispatcher picks, and why, for every benched shape.

    One row per (shape, machine) x dtype x direction: the winning ``Impl``,
    its source (``table``/``tuned`` = measured entry, ``prior`` = analytical
    blocking model, ``*-fallback`` = table winner infeasible here), and the
    canonical table key.  No ``*_us`` fields — these rows never gate; they
    are the record ``check_regression --dispatch-table`` cross-references
    for coverage (every benched shape must resolve through the table or be
    explicitly prior-routed).
    """
    from repro.core.dispatch import (DIRECTIONS, DispatchKey, get_dispatcher,
                                     register_machine)
    disp = get_dispatcher()
    # fused-key variants for the fusion smoke shapes — same tags the table
    # regeneration seeds (benchmarks.tune_dispatch.FUSION_TAGS)
    fusion_tags = {"smoke.res": "res+dz", "smoke.gap": "gap+dz"}
    rows = []
    for s, machine in pairs or [(c, TPU_V5E) for c in CI_SHAPES]:
        register_machine(machine)
        lay = LAY.BlockedConvLayout.choose(s.ci, s.co, groups=s.groups)
        for dtype_name in dtypes:
            for direction in DIRECTIONS:
                fusions = [""]
                if s.name in fusion_tags:
                    fusions.append(fusion_tags[s.name])
                for fusion in fusions:
                    key = DispatchKey.from_shape(s, dtype_name, machine,
                                                 direction, fusion=fusion)
                    dec = disp.decide(key, cob=lay.cb_out, cib=lay.cb_in)
                    rows.append({
                        "layer": s.name, "dtype": dtype_name,
                        "machine": machine.name, "direction": direction,
                        "impl": dec.impl.value, "source": dec.source,
                        "key": key.ident,
                    })
    return rows


def bench_fig1_packing_split(shapes=None, iters=3):
    """Fig. 1: how much of im2col+GEMM is pure packing overhead."""
    rows = []
    for s in shapes or ALEXNET:
        x, w = _inputs(s)
        xp = B.pad_input(x, s.pad, s.hf, s.wf, s.stride)
        packed = jax.jit(lambda x: B.im2col(x, s.hf, s.wf, s.stride))(xp)
        t_pack = time_fn(lambda x: B.im2col(x, s.hf, s.wf, s.stride), xp,
                         iters=iters)
        k = packed.shape[-1]
        wmat = w.reshape(k, s.co)
        t_gemm = time_fn(
            lambda p, wm: (p.reshape(-1, k) @ wm), packed, wmat, iters=iters)
        t_total = time_fn(lambda x, w: B.conv_im2col(x, w, s.stride, s.pad),
                          x, w, iters=iters)
        t_direct = time_fn(lambda x, w: D.direct_conv_nhwc(x, w, s.stride,
                                                           s.pad),
                           x, w, iters=iters)
        rows.append({
            "layer": s.name,
            "pack_us": t_pack * 1e6, "gemm_us": t_gemm * 1e6,
            "im2col_total_us": t_total * 1e6, "direct_us": t_direct * 1e6,
            "packing_fraction": t_pack / max(t_total, 1e-12),
            "direct_vs_gemm_only": t_gemm / t_direct,
        })
    return rows


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="direct-conv timing benchmarks (fig1/fig4 + training "
                    "steps)")
    ap.add_argument("--backward", action="store_true",
                    help="also time fwd+bwd training steps per layer")
    ap.add_argument("--stream", action="store_true",
                    help="also time the streamed halo-DMA kernel variant "
                         "(CI shapes + a pathological deep-pencil shape on "
                         "a tiny MachineModel) with the halo-traffic delta")
    ap.add_argument("--json", default=None,
                    help="write all rows to this JSON file")
    ap.add_argument("--smoke", action="store_true",
                    help="the pinned CI shape set + few iters")
    ap.add_argument("--dtype", action="append", choices=["f32", "bf16"],
                    default=None,
                    help="operand dtype(s) for the training-step rows "
                         "(repeatable; default f32)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations per measurement (default: 5 "
                         "for --smoke — median-of-5 keeps the CI gate off "
                         "the noise floor — else 3)")
    args = ap.parse_args()

    shapes = CI_SHAPES if args.smoke else ZOO
    iters = args.iters if args.iters is not None else (5 if args.smoke else 3)
    dtypes = args.dtype or ["f32"]

    # fig4's baseline comparison stays f32 (the FFT path has no bf16
    # story); the dtype axis lives on the training-step rows.
    report = {"fig4": bench_fig4(shapes, iters=iters)}
    if args.backward:
        report["backward"] = [
            row for d in dtypes
            for row in bench_backward(shapes, iters=iters, dtype_name=d)]
    if args.stream:
        report["stream"] = [
            row for d in dtypes
            for row in bench_stream(iters=iters, dtype_name=d)]

    # the fused-vs-unfused epilogue section always rides along (two shapes,
    # cheap) — its *_us fields gate in CI like every other timing row and
    # its byte column is the fusion accounting (DESIGN.md §14)
    report["fusion"] = [
        row for d in dtypes
        for row in bench_fusion(iters=iters, dtype_name=d)]

    # the routing record: which impl the dispatcher chose for every benched
    # (shape, machine) pair and why (table/tuned/prior) — DESIGN.md §12
    pairs = [(s, TPU_V5E) for s in shapes]
    if args.stream:
        pairs += [p for p in STREAM_SHAPES if p not in pairs]
    report["dispatch"] = dispatch_report(pairs, dtypes=dtypes)

    for section, rows in report.items():
        print(f"== {section} ==")
        for row in rows:
            print("  " + " ".join(
                f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in row.items()))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")
