"""Blocked-layout convolution layers: the paper's §4 design point as an API.

``BlockedConv2D`` keeps its input *and* output in the paper layout
``[N, C/Cb, H, W, Cb]``; stacking layers therefore chains convolutions with
zero NHWC round-trips — no ``nhwc_to_blocked``/``blocked_to_nhwc`` between
layers, which is exactly the "layers compose in the blocked layout without
repacking" claim.  Weights are *stored* in the paper's kernel layout —
grouped-HWIO blocked ``[Co/Cob, Cig/Cbw, Hf, Wf, Cbw, Cob]`` with
``Cig = Ci // groups`` (dense convs have ``Cig = Ci``; depthwise ones
``Cig = 1``) — no transform at call time; bias as channel pencils
``[Co/Cob, Cob]``.  Bias + activation are fused into the convolution
epilogue (DESIGN.md §5).

The full geometry vocabulary rides the layer: ``groups`` opens grouped and
depthwise convolutions (``groups == ci == co``), ``dilation`` opens dilated
taps, and a 1x1/stride-1/unpadded layer routes to the pointwise
channel-matmul fast path — all in the same blocked layout, so a depthwise-
separable block (``DepthwiseSeparableBlock``) chains its two convs with
zero repacks like any other pair of layers (DESIGN.md §13).

Execution routes through the conv dispatch subsystem (DESIGN.md §12): every
call resolves a ``core.dispatch.DispatchKey`` (geometry x dtype x machine x
direction) through a ``ConvDispatcher`` — per-call override, then the
persistent measured table, then the analytical blocking-model prior — and
runs the winning ``Impl``.  All candidates share one semantics and are
fully differentiable; the Pallas families carry custom VJPs routing
``jax.grad`` through their dgrad/wgrad kernels (DESIGN.md §9), so training
runs entirely inside the blocked layout too.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core.blocking import MachineModel, choose_blocking
from repro.core.context import ConvContext, as_context, reject_legacy_kwargs
from repro.core.conv_baselines import Padding, normalize_padding
from repro.core.convspec import as_dilation
from repro.core.direct_conv import direct_conv_blocked
from repro.core.dispatch import (ConvDispatcher, DispatchKey, Impl,
                                 KernelRoute, PALLAS_IMPLS, get_dispatcher,
                                 run_conv_impl)
from repro.core.layout import BlockedConvLayout, choose_pencil, nhwc_to_blocked
from repro.core.precision import Precision, resolve_precision
from repro.kernels.conv2d_common import tree_sum
from repro.kernels.layer_norm import layer_norm_blocked, layer_norm_blocked_ref
from .module import ParamSpec

__all__ = ["BlockedConv2D", "ResidualBlock", "DepthwiseSeparableBlock",
           "BlockedLayerNorm", "ConvNeXtBlock", "PatchifyStem",
           "Downsample", "BlockedCNN", "blocked_global_avg_pool"]


def blocked_global_avg_pool(xb: jnp.ndarray,
                            precision: Union[str, Precision, None] = None
                            ) -> jnp.ndarray:
    """GAP on the blocked layout: [N, C/Cb, H, W, Cb] -> [N, C].

    Reduces spatial dims in the precision policy's *accumulation* dtype —
    not a hardwired up-cast — and flattens the (block, pencil) pair back to
    the channel axis: a reshape, not a layout round-trip (the spatial dims
    are already gone, so there is nothing left to "unpack").  Every shipped
    policy pins accumulation to f32 (DESIGN.md §10), so the default is
    numerically what the old unconditional f32 mean computed, but the
    reduction dtype now follows the policy like every other accumulation
    in the stack.
    """
    n, cblk, _, _, cb = xb.shape
    acc = resolve_precision(precision).accum_dtype
    pooled = jnp.mean(xb.astype(acc), axis=(2, 3))           # [N, C/Cb, Cb]
    return pooled.reshape(n, cblk * cb).astype(xb.dtype)


def _gap_like_window_kernel(y: jnp.ndarray, *, hi: int, wi: int, ci: int,
                            cib: int, hf: int, wf: int, stride: int,
                            padding: Padding, dilation, groups: int,
                            fused_residual: bool, hob, wob,
                            machine: MachineModel,
                            op_bytes: int) -> jnp.ndarray:
    """Pool a blocked conv output the way the fused window kernel does.

    The kernel's ``gap_update`` accumulates one f32 partial sum per spatial
    tile — of the *stored* (already downcast) tile values, reduced by the
    association-fixed ``tree_sum`` — sequentially in grid order (row tiles
    outer, column tiles inner) and divides by the full ``Ho*Wo`` once at
    flush.  Floating-point addition is not associative, so matching the
    fused result bit for bit means replaying that exact grouping: same
    tile sizes (the kernel's own ``choose_blocking`` call), same visit
    order, same per-tile tree reduction.  This is what keeps the jnp impl
    inside ``EXACT_IMPLS`` for gap-fused convs — the serving tier's
    degraded path (DESIGN.md §16) swaps it in for a tripped bucket and
    still owes bit-identical logits.

    Unlike the conv itself (tile-agnostic by design), the pooling program
    necessarily depends on the tile choice — exactly as the kernel's does.
    Geometry the window blocking model cannot fit falls back to one flat
    tile (such shapes route to the streamed family anyway, whose gap is
    tolerance-pinned, not bitwise).
    """
    n, coblk, ho, wo, cob = y.shape
    dil = as_dilation(dilation)
    hf_eff, wf_eff = (hf - 1) * dil[0] + 1, (wf - 1) * dil[1] + 1
    ph, pw = normalize_padding(padding, hf_eff, wf_eff, stride, hi, wi)
    try:
        blk = choose_blocking(hi + ph[0] + ph[1], wi + pw[0] + pw[1],
                              ci, coblk * cob, hf, wf, stride,
                              machine=machine, cob=cob, cib=cib,
                              hob=hob, wob=wob, in_dtype_bytes=op_bytes,
                              groups=groups, dilation=dil,
                              fused_residual=fused_residual, fused_gap=True)
        thob, twob = blk.hob, blk.wob
    except ValueError:
        thob, twob = ho, wo
    f = y.astype(jnp.float32)
    parts = [
        tree_sum(f[:, :, th * thob:(th + 1) * thob,
                   tw * twob:(tw + 1) * twob, :]
                 .reshape(n, coblk, thob * twob, cob), axis=2)
        for th in range(ho // thob) for tw in range(wo // twob)
    ]
    acc = parts[0]
    for part in parts[1:]:
        acc = acc + part
    # same trace-time f32 reciprocal as gap_update: a literal divide can be
    # rewritten to a reciprocal-multiply inside some fusion contexts (1-ulp
    # splits); an explicit multiply survives codegen bit-exactly
    inv_hw = np.float32(1.0) / np.float32(ho * wo)
    return (acc * inv_hw).astype(y.dtype).reshape(n, coblk * cob)


@dataclasses.dataclass(frozen=True)
class BlockedConv2D:
    """Conv2D whose inputs, outputs, weights and bias all live in the paper's
    blocked layouts.  In: [N, Ci/Cib, H, W, Cib] -> out: [N, Co/Cob, Ho, Wo,
    Cob] — same family of layout, so layers chain with no repacking."""

    ci: int
    co: int
    hf: int = 3
    wf: int = 3
    stride: int = 1
    padding: Padding = "SAME"
    activation: Optional[str] = "relu"
    use_bias: bool = True
    groups: int = 1                      # channel groups; groups == ci == co
                                         # is the depthwise special case
    dilation: Union[int, Tuple[int, int]] = 1
    lane: int = 128                      # channel pencil target (TPU: 128)
    hob: Optional[int] = None            # output rows per spatial tile
    wob: Optional[int] = None            # output cols per spatial tile
                                         # (None -> analytical blocking model)
    precision: Union[str, Precision] = "f32"
                                         # mixed-precision policy: params are
                                         # f32 masters; compute casts to the
                                         # policy operand dtype at call time
                                         # (DESIGN.md §10)
    machine: Optional[MachineModel] = None
                                         # VMEM budget the blocking models
                                         # fit against (Pallas path); None
                                         # -> the running device's model
    stream: Optional[bool] = None        # Pallas kernel variant override
                                         # (DESIGN.md §11): None lets the
                                         # dispatcher resolve window-vs-
                                         # stream per direction; True/False
                                         # force one family (dense only)

    def __post_init__(self):
        if self.ci % self.groups or self.co % self.groups:
            raise ValueError(
                f"groups={self.groups} must divide ci={self.ci} and "
                f"co={self.co}")

    @property
    def cig(self) -> int:
        """Per-group input channels — the stored weight's input extent."""
        return self.ci // self.groups

    @property
    def layout(self) -> BlockedConvLayout:
        return BlockedConvLayout.choose(self.ci, self.co, self.lane,
                                        groups=self.groups)

    @property
    def in_pencil(self) -> int:
        return self.layout.cb_in

    @property
    def out_pencil(self) -> int:
        return self.layout.cb_out

    def specs(self):
        lay = self.layout
        fan_in = self.hf * self.wf * self.cig
        s = {"w": ParamSpec(
            (self.co // lay.cb_out, self.cig // lay.cb_weight, self.hf,
             self.wf, lay.cb_weight, lay.cb_out),
            (None,) * 6, init="normal", scale=1.0 / math.sqrt(fan_in))}
        if self.use_bias:
            s["b"] = ParamSpec((self.co // lay.cb_out, lay.cb_out),
                               (None, None), init="zeros")
        return s

    def __call__(self, p, xb: jnp.ndarray, *,
                 context: Optional[ConvContext] = None,
                 residual: Optional[jnp.ndarray] = None,
                 gap: bool = False, **legacy) -> jnp.ndarray:
        """Run this layer through the conv dispatch subsystem.

        ``context`` is the one execution-context object (DESIGN.md §15):
        a frozen :class:`ConvContext` bundling the dispatcher, the forced
        impl, interpret mode, machine model, window-vs-stream and the
        precision policy.  Every field it leaves ``None`` defers to the
        layer's own field or the process default.  (The pre-ISSUE-10 loose
        kwargs are gone; a stale ``impl=``/``dispatch=``/... call raises
        the migration ``TypeError`` naming :class:`ConvContext`.)

        ``context.impl`` forces one candidate and beats every table entry
        (tests and forced paths — ``impl="jnp"`` pins the oracle,
        ``impl="window"`` a Pallas family, and so on).  ``context.stream``
        (or the layer field) forces window-vs-stream inside the dense
        Pallas family.  Every candidate is differentiable — the Pallas
        impls through their custom VJPs, whose dgrad/wgrad directions the
        dispatcher routes independently.

        ``context.precision`` overrides the layer's policy for this call
        (the ``BlockedCNN``/``TrainSettings`` pass-down); params stay f32
        masters either way — the cast to the operand dtype happens inside
        the conv, and its transpose up-casts the weight cotangent back to
        f32.

        ``residual`` fuses a blocked skip tensor (the layer's output shape)
        into the epilogue — ``act(z + b) + residual`` in one pass, no
        post-conv HBM round-trip; ``gap=True`` fuses global average pooling
        into the epilogue and returns ``[N, Co]`` instead of the blocked
        map (DESIGN.md §14).  Both ride the dispatch key's ``fusion`` tag
        so the measured table distinguishes fused from unfused geometry.
        """
        reject_legacy_kwargs("BlockedConv2D", legacy)
        ctx = as_context(context)
        pol = ctx.resolve_precision_for(self.precision)
        machine = ctx.resolve_machine_for(self.machine)
        impl, dispatch, interpret = ctx.impl, ctx.dispatch, ctx.interpret
        bias = p["b"] if self.use_bias else None
        stream = ctx.resolve_stream_for(self.stream)
        toks = [t for t, on in (
            ("res", residual is not None), ("gap", gap),
            ("dz", self.activation not in (None, "linear"))) if on]
        fusion = "+".join(toks)

        decision_impl, route = Impl.JNP, None
        if impl is not None and Impl(impl) is Impl.JNP:
            decision_impl = Impl.JNP        # no dispatcher consult needed
        else:
            disp = dispatch if dispatch is not None else get_dispatcher()
            n, _, hi, wi, _ = xb.shape
            lay = self.layout
            key = DispatchKey.make(
                n, hi, wi, self.ci, self.co, self.hf, self.wf, self.stride,
                self.padding, pol, machine, "fwd",
                groups=self.groups, dilation=self.dilation, fusion=fusion)
            dec = disp.decide(key, override=impl,
                              cob=lay.cb_out, cib=lay.cb_in,
                              hob=self.hob, wob=self.wob)
            decision_impl = dec.impl
            if decision_impl in PALLAS_IMPLS:
                # resolve the backward directions too — one frozen route
                # rides the custom VJP (an explicit stream bool forces all
                # three; otherwise the forward leg is pinned to this
                # decision and dgrad/wgrad resolve independently)
                if isinstance(stream, KernelRoute):
                    route = stream
                elif stream is not None:
                    route = KernelRoute(fwd=stream, dgrad=stream,
                                        wgrad=stream)
                else:
                    kr = disp.kernel_route(key, cob=lay.cb_out,
                                           cib=lay.cb_in, hob=self.hob,
                                           wob=self.wob)
                    route = KernelRoute(
                        fwd=decision_impl is Impl.STREAM,
                        dgrad=kr.dgrad, wgrad=kr.wgrad)

        if decision_impl is Impl.JNP:
            y = direct_conv_blocked(xb, p["w"], self.stride, self.padding,
                                    bias, self.activation,
                                    hob=self.hob, wob=self.wob,
                                    precision=pol, groups=self.groups,
                                    dilation=self.dilation,
                                    residual=residual, gap=False)
            if not gap:
                return y
            # gap-fused: pool the map with the window kernel's exact tile
            # grouping so jnp stays bitwise-exchangeable with the Pallas
            # primary (EXACT_IMPLS) — the breaker demotion relies on it
            return _gap_like_window_kernel(
                y, hi=xb.shape[2], wi=xb.shape[3], ci=self.ci,
                cib=xb.shape[-1], hf=self.hf, wf=self.wf,
                stride=self.stride, padding=self.padding,
                dilation=self.dilation, groups=self.groups,
                fused_residual=residual is not None,
                hob=self.hob, wob=self.wob, machine=machine,
                op_bytes=pol.op_dtype.itemsize)
        return run_conv_impl(decision_impl, xb, p["w"], bias,
                             stride=self.stride, padding=self.padding,
                             activation=self.activation, precision=pol,
                             machine=machine, interpret=interpret,
                             hob=self.hob, wob=self.wob, route=route,
                             dilation=as_dilation(self.dilation),
                             residual=residual, gap=gap)


@dataclasses.dataclass(frozen=True)
class ResidualBlock:
    """Identity-skip block: ``out = act(conv(x) + b) + x``, fused.

    The skip add rides the conv's fused epilogue (DESIGN.md §14) — the
    pre-activation never round-trips to HBM just to be re-read for the add.
    Identity skips need the conv to preserve geometry: ``ci == co``,
    ``stride == 1`` and shape-preserving padding, checked at construction.
    The residual is added *after* the activation in the accumulation dtype
    with one final downcast — the convention the fused epilogue implements
    for every kernel family.
    """

    conv: BlockedConv2D

    def __post_init__(self):
        c = self.conv
        if c.ci != c.co or c.stride != 1:
            raise ValueError(
                "ResidualBlock needs an identity-shaped conv: "
                f"ci={c.ci} co={c.co} stride={c.stride}")

    @property
    def in_pencil(self) -> int:
        return self.conv.in_pencil

    @property
    def out_pencil(self) -> int:
        return self.conv.out_pencil

    @property
    def ci(self) -> int:
        return self.conv.ci

    @property
    def co(self) -> int:
        return self.conv.co

    def specs(self):
        return self.conv.specs()

    def __call__(self, p, xb: jnp.ndarray, **kw) -> jnp.ndarray:
        if kw.pop("residual", None) is not None:
            raise ValueError("ResidualBlock supplies its own skip tensor")
        return self.conv(p, xb, residual=xb, **kw)


@dataclasses.dataclass(frozen=True)
class DepthwiseSeparableBlock:
    """Depthwise conv + pointwise (1x1) conv, chained in the blocked layout.

    The MobileNet factorization on the paper's layout: the depthwise conv
    filters spatially per channel (``groups == ci``, weight ``Cig = 1``) and
    the pointwise conv mixes channels (1x1, the channel-matmul fast path).
    Both legs share the full-lane channel pencil, so the block's interior
    boundary — like its exterior ones — is repack-free; the dispatcher
    routes each leg to its specialized kernel.  Activation convention
    follows MobileNet: nonlinearity after each of the two convs.
    """

    ci: int
    co: int
    hf: int = 3
    wf: int = 3
    stride: int = 1
    padding: Padding = "SAME"
    activation: Optional[str] = "relu"
    use_bias: bool = True
    dilation: Union[int, Tuple[int, int]] = 1
    lane: int = 128
    precision: Union[str, Precision] = "f32"
    machine: Optional[MachineModel] = None

    @property
    def depthwise(self) -> BlockedConv2D:
        return BlockedConv2D(
            ci=self.ci, co=self.ci, hf=self.hf, wf=self.wf,
            stride=self.stride, padding=self.padding,
            activation=self.activation, use_bias=self.use_bias,
            groups=self.ci, dilation=self.dilation, lane=self.lane,
            precision=self.precision, machine=self.machine)

    @property
    def pointwise(self) -> BlockedConv2D:
        return BlockedConv2D(
            ci=self.ci, co=self.co, hf=1, wf=1, stride=1, padding="VALID",
            activation=self.activation, use_bias=self.use_bias,
            lane=self.lane, precision=self.precision, machine=self.machine)

    @property
    def in_pencil(self) -> int:
        return self.depthwise.in_pencil

    @property
    def out_pencil(self) -> int:
        return self.pointwise.out_pencil

    def specs(self):
        return {"dw": self.depthwise.specs(), "pw": self.pointwise.specs()}

    def __call__(self, p, xb: jnp.ndarray, *,
                 context: Optional[ConvContext] = None,
                 residual: Optional[jnp.ndarray] = None,
                 gap: bool = False, **legacy) -> jnp.ndarray:
        reject_legacy_kwargs("DepthwiseSeparableBlock", legacy)
        ctx = as_context(context)
        h = self.depthwise(p["dw"], xb, context=ctx)
        # fused operands land on the channel-mixing leg — the block's output
        return self.pointwise(p["pw"], h, context=ctx,
                              residual=residual, gap=gap)


@dataclasses.dataclass(frozen=True)
class BlockedLayerNorm:
    """LayerNorm over the channels of each pixel, on the blocked layout.

    ``[N, C/Cb, H, W, Cb]`` in and out, no NHWC round trip: statistics in
    f32 over all C channels of a pixel (every pencil block, every lane),
    biased variance, ``eps`` inside the root, an f32 affine ``g``/``b`` per
    channel stored as pencils ``[C/Cb, Cb]``; the output is the policy's
    operand dtype.  The Pallas kernels (``kernels/layer_norm``) run unless
    the context forces the jnp oracle (``impl="jnp"``).  Flat ``[N, C]``
    features (the pooled head) are a map of one pixel.
    """

    c: int
    eps: float = 1e-6
    lane: int = 128
    precision: Union[str, Precision] = "f32"
    machine: Optional[MachineModel] = None

    @property
    def out_pencil(self) -> int:
        return choose_pencil(self.c, self.lane)

    def specs(self):
        shape = (self.c // self.out_pencil, self.out_pencil)
        return {"g": ParamSpec(shape, (None, None), init="ones"),
                "b": ParamSpec(shape, (None, None), init="zeros")}

    def __call__(self, p, xb: jnp.ndarray, *,
                 context: Optional[ConvContext] = None) -> jnp.ndarray:
        ctx = as_context(context)
        pol = ctx.resolve_precision_for(self.precision)
        flat = xb.ndim == 2
        if flat:
            n = xb.shape[0]
            cb = self.out_pencil
            xb = xb.reshape(n, self.c // cb, 1, 1, cb)
        if ctx.impl is not None and Impl(ctx.impl) is Impl.JNP:
            y = layer_norm_blocked_ref(xb.astype(pol.op_dtype), p["g"],
                                       p["b"], eps=self.eps)
        else:
            y = layer_norm_blocked(
                xb, p["g"], p["b"], eps=self.eps, precision=pol,
                machine=ctx.resolve_machine_for(self.machine),
                interpret=ctx.interpret)
        return y.reshape(n, self.c) if flat else y


def _linear_1x1(ci: int, co: int, activation, lane, precision, machine):
    return BlockedConv2D(ci=ci, co=co, hf=1, wf=1, stride=1,
                         padding="VALID", activation=activation, lane=lane,
                         precision=precision, machine=machine)


@dataclasses.dataclass(frozen=True)
class ConvNeXtBlock:
    """The ConvNeXt block (Liu et al. 2022, Fig. 4), on the blocked layout:

        x -> dw kxk + bias -> LN -> pw C->eC + bias, GELU (erf)
          -> pw eC->C + bias -> * gamma -> + x

    The layer scale ``gamma`` (a per-channel parameter) is folded into the
    second pointwise conv at call time, ``gamma (W h + b) = (W diag gamma) h
    + gamma b``, which autodiff differentiates exactly; the skip add rides
    that conv's fused epilogue, and with ``gap`` so does the pool, of the
    sum.  No activation anywhere but the GELU.
    """

    c: int
    k: int = 7
    expansion: int = 4
    eps: float = 1e-6
    lane: int = 128
    precision: Union[str, Precision] = "f32"
    machine: Optional[MachineModel] = None

    @property
    def depthwise(self) -> BlockedConv2D:
        return BlockedConv2D(ci=self.c, co=self.c, hf=self.k, wf=self.k,
                             stride=1, padding="SAME", activation=None,
                             groups=self.c, lane=self.lane,
                             precision=self.precision, machine=self.machine)

    @property
    def norm(self) -> BlockedLayerNorm:
        return BlockedLayerNorm(c=self.c, eps=self.eps, lane=self.lane,
                                precision=self.precision,
                                machine=self.machine)

    @property
    def pw1(self) -> BlockedConv2D:
        return _linear_1x1(self.c, self.expansion * self.c, "gelu_erf",
                           self.lane, self.precision, self.machine)

    @property
    def pw2(self) -> BlockedConv2D:
        return _linear_1x1(self.expansion * self.c, self.c, None, self.lane,
                           self.precision, self.machine)

    @property
    def ci(self) -> int:
        return self.c

    co = ci

    @property
    def in_pencil(self) -> int:
        return self.depthwise.in_pencil

    @property
    def out_pencil(self) -> int:
        return self.pw2.out_pencil

    def specs(self):
        cb = self.pw2.out_pencil
        return {"dw": self.depthwise.specs(), "norm": self.norm.specs(),
                "pw1": self.pw1.specs(), "pw2": self.pw2.specs(),
                "gamma": ParamSpec((self.c // cb, cb), (None, None),
                                   init="ones")}

    def __call__(self, p, xb: jnp.ndarray, *,
                 context: Optional[ConvContext] = None,
                 gap: bool = False) -> jnp.ndarray:
        ctx = as_context(context)
        h = self.depthwise(p["dw"], xb, context=ctx)
        h = self.norm(p["norm"], h, context=ctx)
        h = self.pw1(p["pw1"], h, context=ctx)
        g = p["gamma"]                                   # [C/Cb, Cb]
        pw2 = {"w": p["pw2"]["w"] * g[:, None, None, None, None, :],
               "b": p["pw2"]["b"] * g}
        return self.pw2(pw2, h, context=ctx, residual=xb, gap=gap)


@dataclasses.dataclass(frozen=True)
class _PatchConv:
    """A ``k x k`` stride-``k`` VALID conv with bias (non-overlapping
    patches) and a LayerNorm: ConvNeXt's stem and downsampling layers."""

    ci: int
    co: int
    k: int = 2
    eps: float = 1e-6
    lane: int = 128
    precision: Union[str, Precision] = "f32"
    machine: Optional[MachineModel] = None

    @property
    def conv(self) -> BlockedConv2D:
        return BlockedConv2D(ci=self.ci, co=self.co, hf=self.k, wf=self.k,
                             stride=self.k, padding="VALID", activation=None,
                             lane=self.lane, precision=self.precision,
                             machine=self.machine)

    def _norm(self, c: int) -> BlockedLayerNorm:
        return BlockedLayerNorm(c=c, eps=self.eps, lane=self.lane,
                                precision=self.precision,
                                machine=self.machine)

    @property
    def in_pencil(self) -> int:
        return self.conv.in_pencil

    @property
    def out_pencil(self) -> int:
        return self.conv.out_pencil

    def specs(self):
        return {"conv": self.conv.specs(), "norm": self.norm.specs()}


@dataclasses.dataclass(frozen=True)
class PatchifyStem(_PatchConv):
    """ConvNeXt's stem: the patch conv (4x4 stride 4), then LN over its
    output channels."""

    k: int = 4

    @property
    def norm(self) -> BlockedLayerNorm:
        return self._norm(self.co)

    def __call__(self, p, xb, *, context: Optional[ConvContext] = None,
                 gap: bool = False):
        if gap:
            raise ValueError("the stem cannot be the last layer")
        h = self.conv(p["conv"], xb, context=context)
        return self.norm(p["norm"], h, context=context)


@dataclasses.dataclass(frozen=True)
class Downsample(_PatchConv):
    """ConvNeXt's downsampling layer: LN over the input channels, then the
    patch conv (2x2 stride 2) from ``ci`` to ``co`` channels."""

    @property
    def norm(self) -> BlockedLayerNorm:
        return self._norm(self.ci)

    def __call__(self, p, xb, *, context: Optional[ConvContext] = None,
                 gap: bool = False):
        h = self.norm(p["norm"], xb, context=context)
        return self.conv(p["conv"], h, context=context, gap=gap)


@dataclasses.dataclass(frozen=True)
class BlockedCNN:
    """conv -> ... -> conv -> GAP -> linear head, chained in blocked layout.

    NHWC images are blocked exactly once at entry; every layer boundary after
    that stays in ``[N, C/Cb, H, W, Cb]`` — zero pack/unpack traffic between
    layers (``benchmarks/cnn_zoo.py`` accounts the eliminated bytes).  Layers
    are anything with the blocked-conv calling convention: ``BlockedConv2D``,
    ``DepthwiseSeparableBlock`` and the ConvNeXt layers mix freely.

    ``head_norm`` is a LayerNorm of the pooled features before the head
    (ConvNeXt's), part of the model with its own parameters; ``head_bias``
    gives the head a bias.
    """

    convs: Tuple[BlockedConv2D, ...]
    n_classes: int
    head_norm: Optional[BlockedLayerNorm] = None
    head_bias: bool = False

    def __post_init__(self):
        for a, b in zip(self.convs, self.convs[1:]):
            if a.co != b.ci:
                raise ValueError(f"conv chain breaks: co={a.co} -> ci={b.ci}")
            if a.out_pencil != b.in_pencil:
                raise ValueError(
                    f"pencil mismatch: {a.out_pencil} -> {b.in_pencil}; "
                    "layers must agree on the channel block to chain")

    def specs(self):
        s = {f"conv{i}": c.specs() for i, c in enumerate(self.convs)}
        if self.head_norm is not None:
            s["head_norm"] = self.head_norm.specs()
        s["head"] = ParamSpec((self.convs[-1].co, self.n_classes),
                              (None, None))
        if self.head_bias:
            s["head_bias"] = ParamSpec((self.n_classes,), (None,),
                                       init="zeros")
        return s

    def __call__(self, p, x_nhwc: jnp.ndarray, *,
                 context: Optional[ConvContext] = None,
                 **legacy) -> jnp.ndarray:
        """``context`` (one :class:`ConvContext` — the only spelling; the
        old loose kwargs raise the migration ``TypeError``) rides down to
        every conv (each layer still
        resolves its *own* dispatch key — shapes shrink through the chain,
        so the winning impl may differ per layer).  A ``precision`` it
        carries overrides every conv's policy for this forward — under
        bf16 the layers *chain in bf16* (each conv emits its operand
        dtype), GAP pools in f32, and the head matmul casts its f32 master
        to the feature dtype; logits come back in the compute dtype and
        the loss up-casts them once.  A ``stream`` it carries overrides
        every conv's routing the same way.

        The final conv flows straight into GAP: its fused epilogue
        accumulates the pooled partial sums in f32 scratch and emits
        ``[N, C]`` directly (DESIGN.md §14), so the full feature map of the
        last layer never materializes in HBM."""
        reject_legacy_kwargs("BlockedCNN", legacy)
        ctx = as_context(context)
        # the single layout transform of the whole forward pass
        h = nhwc_to_blocked(x_nhwc, self.convs[0].in_pencil)
        last = len(self.convs) - 1
        for i, conv in enumerate(self.convs):
            h = conv(p[f"conv{i}"], h, context=ctx, gap=(i == last))
        feat = h                      # [N, C] — pooled in the conv epilogue
        if self.head_norm is not None:
            feat = self.head_norm(p["head_norm"], feat, context=ctx)
        logits = feat @ p["head"].astype(feat.dtype)
        if self.head_bias:
            logits = logits + p["head_bias"].astype(logits.dtype)
        return logits
