"""Pallas TPU kernels: blocked 2-D depthwise convolution (DESIGN.md §13).

The depthwise conv is the degenerate group conv — ``groups == Ci == Co``,
one channel per group — so the channel contraction disappears entirely: each
lane of the channel pencil multiplies its own ``Hf x Wf`` tap stack.  That
kills the MXU matmul (there is nothing to contract) and with it the window
kernel's reduction grid axis; what remains is a pure VPU shift-multiply-
accumulate over taps, the 2-D promotion of ``kernels/conv1d_depthwise.py``'s
K-tap shift-and-add.

Layouts: feature maps keep the full-channel pencil ``[N, C/Cb, H, W, Cb]``;
weights are the grouped-HWIO blocked layout at its ``Cig = 1`` extreme,
``[C/Cb, 1, Hf, Wf, 1, Cb]`` — the same six-axis shape as every other conv
weight in the stack (one ``nn.ParamSpec`` covers all of them), with the two
unit axes carrying the "block-diagonal with 1x1 blocks" structure.

Forward grid — note: *no reduction axis*, so there is no accumulator
revisit, no init/flush guard, and no scratch; the f32 accumulator lives in
registers for the lifetime of one grid step:

  grid = (N, C/Cb, Ho/Hob, Wo/Wob)
  x block   [1, 1, Hib, Wib, Cb]      # halo'd patch (dilation-widened)
  w block   [1, 1, Hf, Wf, 1, Cb]     # the whole per-pencil tap stack
  b block   [1, Cb]                   # when bias is given
  out block [1, 1, Hob, Wob, Cb]

dgrad is the forward kernel run on the stride-dilated cotangent with the tap
stack spatially flipped (``w[..., ::-1, ::-1, ...]``) — exactly the
transposed-conv identity, with no pencil swap because there is no pencil
contraction to transpose.  The cotangent is padded by the filter reach less
the forward's own padding (:func:`dgrad_pads`; a negative side crops), so the
launch writes the gradient of the *unpadded* input at its own extents, tiled
like the forward at those extents.  wgrad walks ``(C/Cb, N,
Ho/Hob, Wo/Wob)`` with the last three axes reduced into a resident
``[Hf*Wf, Cb]`` f32 scratch — the per-channel tap gradients — flushed once
into the ``[C/Cb, 1, Hf, Wf, 1, Cb]`` weight-gradient block.

``depthwise_conv2d_blocked_pallas`` carries the family's ``jax.custom_vjp``
(same residual/precision discipline as ``direct_conv2d``: operand casts on
entry, f32 accumulation, pre-activation residual at the policy dtype, one
cotangent up-cast on exit), so a MobileNet-style dw layer trains through
the Pallas path end to end.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret, resolve_machine
from repro.core.blocking import (MachineModel,
                                 choose_depthwise_blocking,
                                 choose_depthwise_wgrad_blocking)
from repro.core.conv_baselines import Padding
from repro.core.convspec import ConvSpec
from repro.core.direct_conv import apply_activation, pad_blocked
from repro.core.precision import F32, Precision, resolve_precision
from .conv2d_common import (bias_spec, compiler_params, cotangent_prologue,
                            crop_lanes, epilogue_flush, first_step,
                            forward_semantics, gap_spec, gap_update,
                            halo_dims, halo_window_spec, lane_pad,
                            last_step, pad_lanes, pencils, strided_source,
                            tap_windows, tile_spec, unpencil, window_steps)

__all__ = ["depthwise_conv2d_blocked_pallas", "depthwise_dgrad_pallas",
           "depthwise_wgrad_pallas", "dgrad_pads"]


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _dw_fwd_kernel(x_ref, w_ref, *rest, hf, wf, hob, wob, stride, dilation,
                   activation, has_bias, has_z=False, prologue_activation=None,
                   has_residual=False, has_gap=False, hw=1):
    """Forward shift-multiply-accumulate; also the dgrad body (flipped taps
    over the dilated cotangent), in which case ``has_z`` rides the saved
    pre-activation through a second halo window and the cotangent prologue
    ``dz = g * act'(z)`` (``prologue_activation`` — the *forward*'s
    activation, distinct from the epilogue's) is applied to the whole patch
    before the taps slide."""
    rest = list(rest)
    z_ref = rest.pop(0) if has_z else None
    b_ref = rest.pop(0) if has_bias else None
    r_ref = rest.pop(0) if has_residual else None
    o_ref = rest.pop(0)
    g_ref = rest.pop(0) if has_gap else None
    gacc_ref = rest.pop(0) if has_gap else None

    patch, lead = x_ref, (0, 0)
    if z_ref is not None:
        patch, lead = cotangent_prologue(x_ref[0, 0], z_ref[0, 0],
                                         prologue_activation), ()

    def taps(src, _, lead):
        # no reduction axis: the accumulator is born and flushed in one step
        # (the taps compute in f32, so a widened source is read as is)
        acc = jnp.zeros((hob * wob, x_ref.shape[-1]), jnp.float32)
        for (dh, dw), win in tap_windows(src, hf, wf, hob, wob, stride,
                                         dilation, lead=lead):
            wtap = w_ref[0, 0, dh, dw, 0]          # [Cb] — own lane only
            acc = acc + (win.astype(jnp.float32)
                         * wtap.astype(jnp.float32)[None, :])
        return acc

    acc = strided_source(patch, stride, taps, lead)
    tile = epilogue_flush(o_ref, acc, hob, wob, b_ref, activation, r_ref)
    if has_gap:
        gap_update(g_ref, gacc_ref, tile, hw,
                   first_step((2, 3)), last_step((2, 3)))


def _dw_wgrad_kernel(x_ref, dy_ref, *rest, hf, wf, hob, wob,
                     stride, dilation, has_z, activation, with_db):
    """Per-channel tap gradients: each tap's window, elementwise against the
    cotangent tile, summed over spatial positions — a [Hf*Wf, Cb] resident
    accumulator instead of the dense kernel's [Hf, Wf, Cib, Cob].

    ``has_z`` forms ``dz = g * act'(z)`` on tile load; ``with_db``
    accumulates ``db = Σ dz`` every step (all three non-channel axes are
    the reduction — there is no ci pass to gate on) into a [1, Cb] f32
    scratch, flushed once per channel block."""
    rest = list(rest)
    z_ref = rest.pop(0) if has_z else None
    o_ref = rest.pop(0)
    db_ref = rest.pop(0) if with_db else None
    acc_ref = rest.pop(0)
    dbacc_ref = rest.pop(0) if with_db else None

    @pl.when(first_step((1, 2, 3)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])
    if z_ref is not None:
        z = z_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])
        dy = cotangent_prologue(dy, z, activation)
    dy = dy.astype(jnp.float32)

    if with_db:
        part = jnp.sum(dy, axis=0, keepdims=True)
        dbacc_ref[...] = jnp.where(first_step((1, 2, 3)), part,
                                   dbacc_ref[...] + part)

        @pl.when(last_step((1, 2, 3)))
        def _db_flush():
            db_ref[0] = dbacc_ref[...].astype(db_ref.dtype)

    def taps(src, _, lead):
        for (dh, dw), win in tap_windows(src, hf, wf, hob, wob, stride,
                                         dilation, lead=lead):
            acc_ref[dh * wf + dw] = acc_ref[dh * wf + dw] + jnp.sum(
                win.astype(jnp.float32) * dy, axis=0)

    strided_source(x_ref, stride, taps)

    @pl.when(last_step((1, 2, 3)))
    def _flush():
        cb = o_ref.shape[-1]
        o_ref[0, 0] = acc_ref[...].reshape(hf, wf, 1, cb).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# launches
# ---------------------------------------------------------------------------

def _dw_forward(xp: jnp.ndarray, w: jnp.ndarray, bias, stride: int,
                activation, hob, wob, machine: MachineModel,
                interpret: bool, dilation=(1, 1), residual=None, gap=False,
                z=None, prologue_activation=None):
    n, cblk, hi, wi, cb = xp.shape
    cblk2, one, hf, wf, one2, cb2 = w.shape
    assert (cblk, cb) == (cblk2, cb2) and one == one2 == 1, \
        (xp.shape, w.shape)
    dil_h, dil_w = dilation
    ho = (hi - ((hf - 1) * dil_h + 1)) // stride + 1
    wo = (wi - ((wf - 1) * dil_w + 1)) // stride + 1

    blk = choose_depthwise_blocking(hi, wi, cblk * cb, hf, wf, stride,
                                    machine=machine, cb=cb, hob=hob, wob=wob,
                                    in_dtype_bytes=xp.dtype.itemsize,
                                    dilation=dilation,
                                    fused_residual=residual is not None,
                                    fused_gap=gap,
                                    fused_prologue=z is not None)
    hob, wob = blk.hob, blk.wob
    hib, wib = halo_dims(hob, wob, hf, wf, stride, dilation)

    steps = window_steps(ho, wo, hob, wob, stride)
    has_bias = bias is not None
    has_z = z is not None
    operands = [xp, w]
    in_specs = [
        halo_window_spec(hib, wib, cb, *steps,
                         lambda b, c, th, tw: (b, c, th, tw)),
        # the weight "matrix" axes are the two unit dims; same blocked
        # layout, Cig=1 extreme
        pl.BlockSpec((1, 1, hf, wf, 1, cb),
                     lambda b, c, th, tw: (c, 0, 0, 0, 0, 0)),
    ]
    if has_z:
        assert z.shape == xp.shape, (z.shape, xp.shape)
        operands.append(z)
        in_specs.append(
            halo_window_spec(hib, wib, cb, *steps,
                             lambda b, c, th, tw: (b, c, th, tw)))
    if has_bias:
        operands.append(pencils(bias))
        in_specs.append(bias_spec(cb, lambda b, c, th, tw: (c,)))
    if residual is not None:
        assert residual.shape == (n, cblk, ho, wo, cb), \
            (residual.shape, (n, cblk, ho, wo, cb))
        operands.append(residual)
        in_specs.append(tile_spec(hob, wob, cb,
                                  lambda b, c, th, tw: (b, c, th, tw)))

    out_specs = tile_spec(hob, wob, cb, lambda b, c, th, tw: (b, c, th, tw))
    out_shape = jax.ShapeDtypeStruct((n, cblk, ho, wo, cb), xp.dtype)
    scratch = []
    if gap:
        out_specs = [out_specs, gap_spec(cb, lambda b, c, th, tw: (b, c))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n, cblk, 1, cb), xp.dtype)]
        scratch.append(pltpu.VMEM((1, cb), jnp.float32))

    grid = (n, cblk, ho // hob, wo // wob)
    return unpencil(pl.pallas_call(
        partial(_dw_fwd_kernel, hf=hf, wf=wf, hob=hob, wob=wob,
                stride=stride, dilation=dilation, activation=activation,
                has_bias=has_bias, has_z=has_z,
                prologue_activation=prologue_activation,
                has_residual=residual is not None, has_gap=gap, hw=ho * wo),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            machine, forward_semantics(gap, reduction=False)),
        interpret=interpret,
    )(*operands), gap)


def dgrad_pads(ho: int, hi: int, hf: int, stride: int = 1,
               dilation: int = 1, pad_lo: int = 0) -> tuple[int, int]:
    """Low/high padding, along one spatial dim, of the stride-dilated
    cotangent (``(ho - 1) * stride + 1`` long) that the depthwise dgrad runs
    over: ``hi + (hf - 1) * dilation`` long in all, so the stride-1 forward
    over it yields the gradient of the forward's *unpadded* ``hi``-long input.
    The low side is the filter reach less the forward's low pad; a negative
    side crops cotangent rows that only ever touched the forward's padding."""
    reach = (hf - 1) * dilation
    lo = reach - pad_lo
    return lo, hi + reach - lo - ((ho - 1) * stride + 1)


@partial(jax.jit, static_argnames=("in_hw", "stride", "pads", "hob", "wob",
                                   "machine", "interpret", "dilation",
                                   "activation"))
def depthwise_dgrad_pallas(dy: jnp.ndarray, w: jnp.ndarray,
                           in_hw: tuple[int, int], stride: int = 1,
                           pads=((0, 0), (0, 0)),
                           hob: Optional[int] = None,
                           wob: Optional[int] = None,
                           machine: Optional[MachineModel] = None,
                           interpret: Optional[bool] = None,
                           dilation=(1, 1),
                           z: Optional[jnp.ndarray] = None,
                           activation: Optional[str] = None) -> jnp.ndarray:
    """Input gradient of the blocked depthwise conv.

    dy: [N, C/Cb, Ho, Wo, Cb] cotangent of a forward over the ``in_hw =
    (Hi, Wi)`` input padded by ``pads = ((ph_lo, ph_hi), (pw_lo, pw_hi))``
    -> [N, C/Cb, Hi, Wi, Cb], the gradient of the *unpadded* input.

    The transposed depthwise conv is itself a depthwise conv: stride-dilate
    the cotangent, pad it by the effective filter reach less the forward's
    own padding (:func:`dgrad_pads` — the forward's pads, transposed), flip
    the tap stack spatially, and run the forward kernel at stride 1 (forward
    filter dilation still strides the taps).  Each element sums the same
    taps in the same order as a full-halo dgrad cropped afterwards, so the
    result is bit-identical to it, without the crop pass or the halo rows.

    ``z``/``activation`` fuse the activation prologue: ``z`` is the saved
    pre-activation map (same shape as ``dy``), dilated and padded alongside
    the cotangent so the kernel forms ``dz = g * act'(z)`` on tile load —
    the dilation zeros stay zero because the prologue is elementwise."""
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    ho, wo = dy.shape[2:4]
    hf, wf = w.shape[2:4]
    (ph_lo, _), (pw_lo, _) = pads
    edges = (dgrad_pads(ho, in_hw[0], hf, stride, dilation[0], ph_lo),
             dgrad_pads(wo, in_hw[1], wf, stride, dilation[1], pw_lo))
    config = ((0, 0, 0), (0, 0, 0),
              *((lo, hi, stride - 1) for lo, hi in edges), (0, 0, 0))

    def _dilate_pad(t):
        # interior padding stride-dilates; negative edges crop
        return jax.lax.pad(t, jnp.zeros((), t.dtype), config)

    dyp = _dilate_pad(dy)
    zp = None if z is None else _dilate_pad(z)
    wf_flip = w[:, :, ::-1, ::-1, :, :]
    return _dw_forward(dyp, wf_flip, None, 1, None, hob, wob, machine,
                       interpret, dilation, z=zp,
                       prologue_activation=activation)


@partial(jax.jit, static_argnames=("hf", "wf", "stride", "hob", "wob",
                                   "machine", "interpret", "out_dtype",
                                   "dilation", "activation", "with_db"))
def depthwise_wgrad_pallas(xp: jnp.ndarray, dy: jnp.ndarray,
                           hf: int, wf: int, stride: int = 1,
                           hob: Optional[int] = None,
                           wob: Optional[int] = None,
                           machine: Optional[MachineModel] = None,
                           interpret: Optional[bool] = None,
                           out_dtype=None,
                           dilation=(1, 1),
                           z: Optional[jnp.ndarray] = None,
                           activation: Optional[str] = None,
                           with_db: bool = False):
    """Weight gradient of the VALID blocked depthwise conv.

    xp: [N, C/Cb, Hi, Wi, Cb] the forward's *padded* input;
    dy: [N, C/Cb, Ho, Wo, Cb] cotangent
    -> [C/Cb, 1, Hf, Wf, 1, Cb] in the grouped-HWIO blocked layout.
    (N, Ho/Hob, Wo/Wob) are the reduction axes; the [Hf*Wf, Cb] accumulator
    stays resident per channel block.

    ``z``/``activation`` fuse ``dz = g * act'(z)`` on tile load (``z`` has
    ``dy``'s shape — the saved pre-activation).  ``with_db`` additionally
    returns ``(dw, db)`` with ``db = Σ dz`` accumulated f32 in-kernel,
    shape ``[C/Cb, Cb]``."""
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    n, cblk, hi, wi, cb = xp.shape
    n2, cblk2, ho, wo, cb2 = dy.shape
    assert (n, cblk, cb) == (n2, cblk2, cb2), (xp.shape, dy.shape)

    blk = choose_depthwise_wgrad_blocking(
        ho, wo, hf, wf, stride, machine=machine, cb=cb, hob=hob, wob=wob,
        in_dtype_bytes=xp.dtype.itemsize, dilation=dilation,
        fused_prologue=z is not None, fused_bias=with_db)
    hob, wob = blk.hob, blk.wob
    hib, wib = halo_dims(hob, wob, hf, wf, stride, dilation)

    has_z = z is not None
    operands = [xp, dy]
    in_specs = [
        halo_window_spec(hib, wib, cb,
                         *window_steps(ho, wo, hob, wob, stride),
                         lambda c, b, th, tw: (b, c, th, tw)),
        tile_spec(hob, wob, cb, lambda c, b, th, tw: (b, c, th, tw)),
    ]
    if has_z:
        assert z.shape == dy.shape, (z.shape, dy.shape)
        operands.append(z)
        in_specs.append(tile_spec(hob, wob, cb,
                                  lambda c, b, th, tw: (b, c, th, tw)))

    out_specs = pl.BlockSpec((1, 1, hf, wf, 1, cb),
                             lambda c, b, th, tw: (c, 0, 0, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((cblk, 1, hf, wf, 1, cb),
                                     out_dtype or xp.dtype)
    scratch = [pltpu.VMEM((hf * wf, cb), jnp.float32)]
    if with_db:
        out_specs = [out_specs, bias_spec(cb, lambda c, b, th, tw: (c,))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((cblk, 1, cb), jnp.float32)]
        scratch.append(pltpu.VMEM((1, cb), jnp.float32))

    grid = (cblk, n, ho // hob, wo // wob)
    return unpencil(pl.pallas_call(
        partial(_dw_wgrad_kernel, hf=hf, wf=wf, hob=hob, wob=wob,
                stride=stride, dilation=dilation, has_z=has_z,
                activation=activation, with_db=with_db),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            machine, ("parallel",) + ("arbitrary",) * 3),
        interpret=interpret,
    )(*operands), with_db)


# ---------------------------------------------------------------------------
# custom VJP + public entry point
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _dwconv(x, w, bias, residual, spec, activation, hob, wob, machine,
            interpret, precision, gap):
    op = precision.op_dtype
    xp = pad_blocked(x.astype(op), *spec.pads)
    r = None if residual is None else residual.astype(op)
    out = _dw_forward(xp, w.astype(op), bias, spec.stride, activation,
                      hob, wob, machine, interpret, spec.dilation,
                      residual=r, gap=gap)
    if gap:
        _, pooled = out
        n, cblk, cb = pooled.shape
        return pooled.reshape(n, cblk * cb)
    return out


def _dwconv_fwd(x, w, bias, residual, spec, activation, hob, wob, machine,
                interpret, precision, gap):
    op = precision.op_dtype
    xp = pad_blocked(x.astype(op), *spec.pads)
    wq = w.astype(op)
    z = _dw_forward(xp, wq, bias, spec.stride, None, hob, wob, machine,
                    interpret, spec.dilation)
    linear = activation in (None, "linear")
    out = z if linear else apply_activation(
        z.astype(jnp.float32), activation).astype(z.dtype)
    if residual is not None:
        out = (out.astype(jnp.float32)
               + residual.astype(jnp.float32)).astype(z.dtype)
    if gap:
        n, cblk, _, _, cb = z.shape
        out = jnp.mean(out.astype(jnp.float32),
                       axis=(2, 3)).reshape(n, cblk * cb).astype(z.dtype)
    res = (xp, wq, bias,
           None if linear else z.astype(precision.residual_dtype),
           None if residual is None else jnp.zeros((0,), residual.dtype),
           jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))
    return out, res


def _dwconv_bwd(spec, activation, hob, wob, machine, interpret, precision,
                gap, res, g):
    xp, wq, bias, z, r_token, x_token, w_token = res
    hf, wf = wq.shape[2], wq.shape[3]
    stride, dilation = spec.stride, spec.dilation
    dil_h, dil_w = dilation

    if gap:
        hi_p0, wi_p0 = xp.shape[2], xp.shape[3]
        ho = (hi_p0 - ((hf - 1) * dil_h + 1)) // stride + 1
        wo = (wi_p0 - ((wf - 1) * dil_w + 1)) // stride + 1
        n = xp.shape[0]
        cblk, cb = wq.shape[0], wq.shape[-1]
        gm = g.reshape(n, cblk, 1, 1, cb).astype(jnp.float32) / (ho * wo)
        g = jnp.broadcast_to(gm, (n, cblk, ho, wo, cb))
    g = g.astype(precision.op_dtype)
    dres = None if r_token is None else g.astype(r_token.dtype)
    zs = None if z is None else z.astype(g.dtype)

    dx = depthwise_dgrad_pallas(g, wq, (spec.hi, spec.wi), stride=stride,
                                pads=spec.pads, machine=machine,
                                interpret=interpret, dilation=dilation,
                                z=zs, activation=activation
                                ).astype(x_token.dtype)

    if bias is not None:
        dw, db32 = depthwise_wgrad_pallas(
            xp, g, hf, wf, stride=stride, machine=machine,
            interpret=interpret, out_dtype=jnp.float32, dilation=dilation,
            z=zs, activation=activation, with_db=True)
        db = db32.astype(bias.dtype)
    else:
        dw = depthwise_wgrad_pallas(
            xp, g, hf, wf, stride=stride, machine=machine,
            interpret=interpret, out_dtype=jnp.float32, dilation=dilation,
            z=zs, activation=activation)
        db = None
    dw = dw.astype(w_token.dtype)
    return dx, dw, db, dres


_dwconv.defvjp(_dwconv_fwd, _dwconv_bwd)


@partial(jax.jit,
         static_argnames=("stride", "padding", "activation", "hob", "wob",
                          "machine", "interpret", "precision", "dilation",
                          "gap"))
def depthwise_conv2d_blocked_pallas(x: jnp.ndarray, w: jnp.ndarray,
                                    bias: Optional[jnp.ndarray] = None,
                                    stride: int = 1,
                                    padding: Padding = "VALID",
                                    activation: Optional[str] = None,
                                    hob: Optional[int] = None,
                                    wob: Optional[int] = None,
                                    machine: Optional[MachineModel] = None,
                                    interpret: Optional[bool] = None,
                                    precision: Precision | str = F32,
                                    dilation: int | tuple = 1,
                                    residual: Optional[jnp.ndarray] = None,
                                    gap: bool = False):
    """Tiled + fused blocked depthwise convolution, differentiable end to
    end through its own Pallas dgrad/wgrad kernels.

    x: [N, C/Cb, Hi, Wi, Cb]; w: [C/Cb, 1, Hf, Wf, 1, Cb] (grouped-HWIO
    blocked at Cig=1); bias: [C/Cb, Cb] or None
    -> [N, C/Cb, Ho, Wo, Cb] in the policy's operand dtype.

    Same padding/precision contracts as ``direct_conv2d_blocked_pallas``,
    and the same §14 fusion riders: ``residual`` (post-activation add of an
    output-shaped map, f32 on the accumulator, one downcast) and ``gap``
    (per-tile f32 partial-sum global average pool — returns the flat
    ``[N, C]`` pooled features instead of the map).  No ``stream`` knob —
    the depthwise working set (no weight matrix, no reduction) fits VMEM
    wherever the dense window kernel's does.
    """
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    n, cblk, hi, wi, cb = x.shape
    (p,) = lane_pad(machine, interpret, cb)
    c = cblk * (cb + p)
    spec = ConvSpec.make(n, hi, wi, c, c, w.shape[2], w.shape[3],
                         stride=stride, padding=padding, groups=c,
                         dilation=dilation)
    out = _dwconv(pad_lanes(x, p), pad_lanes(w, p), pad_lanes(bias, p),
                  pad_lanes(residual, p), spec, activation, hob, wob,
                  machine, interpret, resolve_precision(precision), gap)
    return crop_lanes(out, cb, gap, cblk)
