"""Pallas TPU kernels: the 1x1-as-matmul fast path (DESIGN.md §13).

A 1x1 stride-1 unpadded dense conv is a channel matmul applied at every
spatial position — ``ConvSpec.is_pointwise``.  The window kernel computes
it correctly but drags the halo machinery along for a halo of size zero:
element-offset (``pl.Element``) indexing, one strided ``tap_windows`` view,
a ``(Hob-1)*stride + 1`` window that is exactly the tile.  This family
strips all of it: plain Blocked BlockSpecs, one MXU matmul per grid step.

Forward grid (the window schedule minus the taps):

  grid = (N, Co/Cob, Ho/Hob, Wo/Wob, Ci/Cib)   # last axis is the reduction
  x block   [1, 1, Hob, Wob, Cib]     # the tile IS the window
  w block   [1, 1, 1, 1, Cib, Cob]    # a [Cib, Cob] matrix in conv clothing
  b block   [1, Cob]
  out block [1, 1, Hob, Wob, Cob]     # f32 scratch accumulator across Ci

dgrad swaps the pencils (``dy @ w`` contracting Cob — the transposed
matmul; no cotangent dilation, no halo pad, no mirrored taps), wgrad makes
(N, Ho/Hob, Wo/Wob) the reduction into a resident ``[Cib, Cob]`` f32 block
(``x_tileᵀ @ dy_tile`` contracting spatial positions).  Under a fused
activation the dgrad runs on ``(N, Ho/Hob, Wo/Wob, Co/Cob)`` with every
input-channel block in each step, so it forms ``dz = g * act'(z)`` once
per element, and writes ``dz`` back for the wgrad to read.

``pointwise_conv2d_blocked_pallas`` carries the family's ``jax.custom_vjp``
with the same precision discipline as the other families.  The entry point
*requires* pointwise geometry (stride 1, no pads, groups 1, dilation 1) —
the dispatcher only routes it where ``ConvSpec.is_pointwise`` holds.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret, resolve_machine
from repro.core.blocking import (MachineModel,
                                 choose_pointwise_blocking,
                                 choose_pointwise_dgrad_blocking,
                                 choose_pointwise_wgrad_blocking)
from repro.core.direct_conv import apply_activation
from repro.core.padding import normalize_padding
from repro.core.precision import F32, Precision, resolve_precision
from repro.utils.spans import now, record
from .conv2d_common import (bias_spec, compiler_params, cotangent_prologue,
                            epilogue_flush, first_step, forward_semantics,
                            gap_spec, gap_update, last_step, pencils,
                            tile_spec, unpencil, weight_spec,
                            wgrad_semantics)

__all__ = ["pointwise_conv2d_blocked_pallas", "pointwise_dgrad_pallas",
           "pointwise_wgrad_pallas"]


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _pw_fwd_kernel(x_ref, w_ref, *rest, hob, wob, activation, has_bias,
                   has_residual=False, has_gap=False, hw=1):
    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    r_ref = rest.pop(0) if has_residual else None
    o_ref = rest.pop(0)
    g_ref = rest.pop(0) if has_gap else None
    acc_ref = rest.pop(0)
    gacc_ref = rest.pop(0) if has_gap else None

    # program_id may not be issued inside a pl.when body — compute the gap
    # tile predicates here and pass them in as values
    gap_first = first_step((2, 3)) if has_gap else None
    gap_last = last_step((2, 3)) if has_gap else None

    @pl.when(first_step((4,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0].reshape(hob * wob, x_ref.shape[-1])
    acc_ref[...] = acc_ref[...] + jnp.dot(
        x, w_ref[0, 0, 0, 0], preferred_element_type=jnp.float32)

    @pl.when(last_step((4,)))
    def _flush():
        tile = epilogue_flush(o_ref, acc_ref[...], hob, wob, b_ref,
                              activation, r_ref)
        if has_gap:
            gap_update(g_ref, gacc_ref, tile, hw, gap_first, gap_last)


def _pw_dgrad_kernel(dy_ref, w_ref, o_ref, acc_ref, *, hob, wob):
    """Transposed channel matmul: contract the Cob lanes of the cotangent
    against the weight matrix's output axis."""
    @pl.when(first_step((4,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])
    # [Hob*Wob, Cob] x [Cib, Cob] -> [Hob*Wob, Cib]
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        dy, w_ref[0, 0, 0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_step((4,)))
    def _flush():
        epilogue_flush(o_ref, acc_ref[...], hob, wob)


def _pw_dgrad_prologue_kernel(dy_ref, z_ref, w_ref, o_ref, dz_ref, acc_ref,
                              *, hob, wob, activation):
    """The dgrad with the activation prologue, on the grid ``(n, th, tw,
    co)``: each step forms ``dz = g * act'(z)`` once for its cotangent
    tile, writes it back for the wgrad, and contracts it against every
    input-channel block of the weight into one f32 accumulator per block
    (``acc[c] += dz @ w[co, c]ᵀ``) — the same per-``co`` accumulation
    order as the prologue-free kernel, so dx keeps its bits."""
    ciblk, cob = w_ref.shape[1], dy_ref.shape[-1]

    @pl.when(first_step((3,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dz = cotangent_prologue(dy_ref[0, 0].reshape(hob * wob, cob),
                            z_ref[0, 0].reshape(hob * wob, cob), activation)
    dz_ref[0, 0] = dz.reshape(hob, wob, cob)
    for c in range(ciblk):
        acc_ref[c] = acc_ref[c] + jax.lax.dot_general(
            dz, w_ref[0, c, 0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last_step((3,)))
    def _flush():
        for c in range(ciblk):
            o_ref[0, c] = acc_ref[c].reshape(
                hob, wob, o_ref.shape[-1]).astype(o_ref.dtype)


def _pw_wgrad_kernel(x_ref, dy_ref, *rest, hob, wob, with_db):
    """Weight gradient: contract the spatial positions of the x tile against
    the cotangent tile into a resident [Cib, Cob] block.

    ``with_db`` accumulates ``db = Σ dy`` into a [1, Cob] f32 scratch on
    the ci == 0 pass only (the (n, th, tw) reduction visits every tile
    exactly once per ci step), flushed once per co block."""
    rest = list(rest)
    o_ref = rest.pop(0)
    db_ref = rest.pop(0) if with_db else None
    acc_ref = rest.pop(0)
    dbacc_ref = rest.pop(0) if with_db else None

    @pl.when(first_step((2, 3, 4)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0].reshape(hob * wob, x_ref.shape[-1])
    dy = dy_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])

    if with_db:
        db_first = first_step((2, 3, 4))

        @pl.when(pl.program_id(1) == 0)
        def _db_accum():
            part = jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)
            dbacc_ref[...] = jnp.where(db_first, part, dbacc_ref[...] + part)

        @pl.when(last_step((1, 2, 3, 4)))
        def _db_flush():
            db_ref[0] = dbacc_ref[...].astype(db_ref.dtype)

    # [Hob*Wob, Cib] x [Hob*Wob, Cob] -> [Cib, Cob]
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last_step((2, 3, 4)))
    def _flush():
        o_ref[0, 0, 0, 0] = acc_ref[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# launches
# ---------------------------------------------------------------------------

def _pw_forward(x: jnp.ndarray, w: jnp.ndarray, bias, activation, hob, wob,
                machine: MachineModel, interpret: bool, residual=None,
                gap=False):
    n, ciblk, hi, wi, cib = x.shape
    coblk, ciblk2, one, one2, cib2, cob = w.shape
    assert (ciblk, cib) == (ciblk2, cib2) and one == one2 == 1, \
        (x.shape, w.shape)

    blk = choose_pointwise_blocking(hi, wi, ciblk * cib, coblk * cob,
                                    machine=machine, cob=cob, cib=cib,
                                    hob=hob, wob=wob,
                                    in_dtype_bytes=x.dtype.itemsize,
                                    fused_residual=residual is not None,
                                    fused_gap=gap)
    hob, wob = blk.hob, blk.wob

    has_bias = bias is not None
    operands = [x, w]
    in_specs = [
        # plain Blocked tiles — the whole point of the fast path: no
        # element-offset window, no halo
        tile_spec(hob, wob, cib, lambda b, co, th, tw, ci: (b, ci, th, tw)),
        weight_spec(1, 1, cib, cob, lambda b, co, th, tw, ci: (co, ci)),
    ]
    if has_bias:
        operands.append(pencils(bias))
        in_specs.append(bias_spec(cob, lambda b, co, th, tw, ci: (co,)))
    if residual is not None:
        assert residual.shape == (n, coblk, hi, wi, cob), \
            (residual.shape, (n, coblk, hi, wi, cob))
        operands.append(residual)
        in_specs.append(tile_spec(hob, wob, cob,
                                  lambda b, co, th, tw, ci: (b, co, th, tw)))

    out_specs = tile_spec(hob, wob, cob,
                          lambda b, co, th, tw, ci: (b, co, th, tw))
    out_shape = jax.ShapeDtypeStruct((n, coblk, hi, wi, cob), x.dtype)
    scratch = [pltpu.VMEM((hob * wob, cob), jnp.float32)]
    if gap:
        out_specs = [out_specs,
                     gap_spec(cob, lambda b, co, th, tw, ci: (b, co))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n, coblk, 1, cob), x.dtype)]
        scratch.append(pltpu.VMEM((1, cob), jnp.float32))

    grid = (n, coblk, hi // hob, wi // wob, ciblk)
    return unpencil(pl.pallas_call(
        partial(_pw_fwd_kernel, hob=hob, wob=wob, activation=activation,
                has_bias=has_bias, has_residual=residual is not None,
                has_gap=gap, hw=hi * wi),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(machine, forward_semantics(gap)),
        interpret=interpret,
    )(*operands), gap)


@partial(jax.jit, static_argnames=("hob", "wob", "machine", "interpret",
                                   "activation"))
def pointwise_dgrad_pallas(dy: jnp.ndarray, w: jnp.ndarray,
                           hob: Optional[int] = None,
                           wob: Optional[int] = None,
                           machine: Optional[MachineModel] = None,
                           interpret: Optional[bool] = None,
                           z: Optional[jnp.ndarray] = None,
                           activation: Optional[str] = None):
    """Input gradient of the pointwise conv — the transposed channel matmul.
    No dilation, no halo pad: dx has the input's spatial extents already.

    With ``z`` (the saved pre-activation, shape of ``dy``) and
    ``activation`` the launch forms ``dz = g * act'(z)`` once per element
    and returns ``(dx, dz)``: ``dz`` in ``dy``'s dtype, written over
    ``dy``'s buffer where XLA can give it up, for the weight gradient to
    read.  Each such launch records ``kernels.pw_prologue`` while it is
    traced (DESIGN.md §14)."""
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    n, coblk, ho, wo, cob = dy.shape
    coblk2, ciblk, one, one2, cib, cob2 = w.shape
    assert (coblk, cob) == (coblk2, cob2) and one == one2 == 1, \
        (dy.shape, w.shape)
    if z is not None:
        assert z.shape == dy.shape, (z.shape, dy.shape)
        return _pw_dgrad_prologue(dy, z, w, activation, hob, wob, machine,
                                  interpret)

    # the transposed matmul's pencils swap: cib becomes the lane (output)
    # pencil, cob the contraction depth
    blk = choose_pointwise_blocking(ho, wo, coblk * cob, ciblk * cib,
                                    machine=machine, cob=cib, cib=cob,
                                    hob=hob, wob=wob,
                                    in_dtype_bytes=dy.dtype.itemsize)
    hob, wob = blk.hob, blk.wob
    grid = (n, ciblk, ho // hob, wo // wob, coblk)
    return pl.pallas_call(
        partial(_pw_dgrad_kernel, hob=hob, wob=wob),
        grid=grid,
        in_specs=[
            tile_spec(hob, wob, cob,
                      lambda b, ci, th, tw, co: (b, co, th, tw)),
            weight_spec(1, 1, cib, cob, lambda b, ci, th, tw, co: (co, ci)),
        ],
        out_specs=tile_spec(hob, wob, cib,
                            lambda b, ci, th, tw, co: (b, ci, th, tw)),
        out_shape=jax.ShapeDtypeStruct((n, ciblk, ho, wo, cib), dy.dtype),
        scratch_shapes=[pltpu.VMEM((hob * wob, cib), jnp.float32)],
        compiler_params=compiler_params(
            machine, ("parallel",) * 4 + ("arbitrary",)),
        interpret=interpret,
    )(dy, w)


def _pw_dgrad_prologue(dy, z, w, activation, hob, wob, machine, interpret):
    """The prologue dgrad's launch (see :func:`pointwise_dgrad_pallas`):
    grid ``(n, th, tw, co)`` with ``co`` the reduction, every input-channel
    block in each step, the dx block resident across ``co``."""
    n, coblk, ho, wo, cob = dy.shape
    _, ciblk, _, _, cib, _ = w.shape
    blk = choose_pointwise_dgrad_blocking(
        ho, wo, ciblk, machine=machine, cob=cob, cib=cib, hob=hob, wob=wob,
        in_dtype_bytes=dy.dtype.itemsize)
    hob, wob = blk.hob, blk.wob
    grid = (n, ho // hob, wo // wob, coblk)
    cot = tile_spec(hob, wob, cob, lambda b, th, tw, co: (b, co, th, tw))
    # grid steps over cotangent tiles: how often each dz element is formed
    evaluations = math.prod(grid) // (n * coblk * grid[1] * grid[2])
    t = now()
    record("kernels.pw_prologue", t, t, attrs={
        "direction": "dgrad", "ci_blocks": ciblk, "rows": hob * wob,
        "evaluations": evaluations})
    return pl.pallas_call(
        partial(_pw_dgrad_prologue_kernel, hob=hob, wob=wob,
                activation=activation),
        grid=grid,
        in_specs=[
            cot, cot,
            pl.BlockSpec((1, ciblk, 1, 1, cib, cob),
                         lambda b, th, tw, co: (co, 0, 0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ciblk, hob, wob, cib),
                         lambda b, th, tw, co: (b, 0, th, tw, 0)),
            cot,
        ],
        out_shape=[jax.ShapeDtypeStruct((n, ciblk, ho, wo, cib), dy.dtype),
                   jax.ShapeDtypeStruct(dy.shape, dy.dtype)],
        scratch_shapes=[pltpu.VMEM((ciblk, hob * wob, cib), jnp.float32)],
        # each cotangent tile is read and its dz written in the same step
        input_output_aliases={0: 1},
        compiler_params=compiler_params(
            machine, ("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(dy, z, w)


@partial(jax.jit, static_argnames=("hob", "wob", "machine", "interpret",
                                   "out_dtype", "with_db"))
def pointwise_wgrad_pallas(x: jnp.ndarray, dy: jnp.ndarray,
                           hob: Optional[int] = None,
                           wob: Optional[int] = None,
                           machine: Optional[MachineModel] = None,
                           interpret: Optional[bool] = None,
                           out_dtype=None,
                           with_db: bool = False):
    """Weight gradient of the pointwise conv: Σ_tiles x_tileᵀ @ dy_tile into
    the [Co/Cob, Ci/Cib, 1, 1, Cib, Cob] blocked weight layout.

    ``dy`` is the cotangent of the conv's own output — under a fused
    activation the ``dz`` the prologue dgrad formed.  ``with_db``
    additionally returns ``(dw, db)`` with ``db = Σ dy`` accumulated f32
    in-kernel, shape ``[Co/Cob, Cob]``."""
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    n, ciblk, hi, wi, cib = x.shape
    n2, coblk, ho, wo, cob = dy.shape
    assert (n, hi, wi) == (n2, ho, wo), (x.shape, dy.shape)

    blk = choose_pointwise_wgrad_blocking(
        ho, wo, machine=machine, cob=cob, cib=cib, hob=hob, wob=wob,
        in_dtype_bytes=x.dtype.itemsize, fused_bias=with_db)
    hob, wob = blk.hob, blk.wob

    in_specs = [
        tile_spec(hob, wob, cib,
                  lambda co, ci, b, th, tw: (b, ci, th, tw)),
        tile_spec(hob, wob, cob,
                  lambda co, ci, b, th, tw: (b, co, th, tw)),
    ]

    out_specs = weight_spec(1, 1, cib, cob,
                            lambda co, ci, b, th, tw: (co, ci))
    out_shape = jax.ShapeDtypeStruct((coblk, ciblk, 1, 1, cib, cob),
                                     out_dtype or x.dtype)
    scratch = [pltpu.VMEM((cib, cob), jnp.float32)]
    if with_db:
        out_specs = [out_specs,
                     bias_spec(cob, lambda co, ci, b, th, tw: (co,))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((coblk, 1, cob), jnp.float32)]
        scratch.append(pltpu.VMEM((1, cob), jnp.float32))

    grid = (coblk, ciblk, n, ho // hob, wo // wob)
    return unpencil(pl.pallas_call(
        partial(_pw_wgrad_kernel, hob=hob, wob=wob, with_db=with_db),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(machine, wgrad_semantics(with_db)),
        interpret=interpret,
    )(x, dy), with_db)


# ---------------------------------------------------------------------------
# custom VJP + public entry point
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _pwconv(x, w, bias, residual, activation, hob, wob, machine, interpret,
            precision, gap):
    op = precision.op_dtype
    r = None if residual is None else residual.astype(op)
    out = _pw_forward(x.astype(op), w.astype(op), bias, activation, hob,
                      wob, machine, interpret, residual=r, gap=gap)
    if gap:
        _, pooled = out
        n, coblk, cob = pooled.shape
        return pooled.reshape(n, coblk * cob)
    return out


def _pwconv_fwd(x, w, bias, residual, activation, hob, wob, machine,
                interpret, precision, gap):
    op = precision.op_dtype
    xq, wq = x.astype(op), w.astype(op)
    z = _pw_forward(xq, wq, bias, None, hob, wob, machine, interpret)
    linear = activation in (None, "linear")
    out = z if linear else apply_activation(
        z.astype(jnp.float32), activation).astype(z.dtype)
    if residual is not None:
        out = (out.astype(jnp.float32)
               + residual.astype(jnp.float32)).astype(z.dtype)
    if gap:
        n, coblk, _, _, cob = z.shape
        out = jnp.mean(out.astype(jnp.float32),
                       axis=(2, 3)).reshape(n, coblk * cob).astype(z.dtype)
    res = (xq, wq, bias,
           None if linear else z.astype(precision.residual_dtype),
           None if residual is None else jnp.zeros((0,), residual.dtype),
           jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))
    return out, res


def _pwconv_bwd(activation, hob, wob, machine, interpret, precision, gap,
                res, g):
    """No pad/dilate bookkeeping anywhere: the pointwise backward is two
    more channel matmuls over the same tiles.  The dgrad forms the
    activation prologue's ``dz`` once and hands it to the wgrad, which
    folds the bias cotangent into its flush."""
    xq, wq, bias, z, r_token, x_token, w_token = res

    if gap:
        n, ciblk, hi, wi, cib = xq.shape
        coblk, cob = wq.shape[0], wq.shape[-1]
        gm = g.reshape(n, coblk, 1, 1, cob).astype(jnp.float32) / (hi * wi)
        g = jnp.broadcast_to(gm, (n, coblk, hi, wi, cob))
    g = g.astype(precision.op_dtype)
    dres = None if r_token is None else g.astype(r_token.dtype)

    if z is None:
        dx = pointwise_dgrad_pallas(g, wq, machine=machine,
                                    interpret=interpret)
        dz = g
    else:
        dx, dz = pointwise_dgrad_pallas(
            g, wq, machine=machine, interpret=interpret,
            z=z.astype(g.dtype), activation=activation)
    dx = dx.astype(x_token.dtype)
    if bias is not None:
        dw, db32 = pointwise_wgrad_pallas(
            xq, dz, machine=machine, interpret=interpret,
            out_dtype=jnp.float32, with_db=True)
        db = db32.astype(bias.dtype)
    else:
        dw = pointwise_wgrad_pallas(
            xq, dz, machine=machine, interpret=interpret,
            out_dtype=jnp.float32)
        db = None
    dw = dw.astype(w_token.dtype)
    return dx, dw, db, dres


_pwconv.defvjp(_pwconv_fwd, _pwconv_bwd)


@partial(jax.jit,
         static_argnames=("stride", "padding", "activation", "hob", "wob",
                          "machine", "interpret", "precision", "gap"))
def pointwise_conv2d_blocked_pallas(x: jnp.ndarray, w: jnp.ndarray,
                                    bias: Optional[jnp.ndarray] = None,
                                    stride: int = 1,
                                    padding="VALID",
                                    activation: Optional[str] = None,
                                    hob: Optional[int] = None,
                                    wob: Optional[int] = None,
                                    machine: Optional[MachineModel] = None,
                                    interpret: Optional[bool] = None,
                                    precision: Precision | str = F32,
                                    residual: Optional[jnp.ndarray] = None,
                                    gap: bool = False):
    """Fused 1x1-as-matmul blocked conv, differentiable end to end.

    x: [N, Ci/Cib, H, W, Cib]; w: [Co/Cob, Ci/Cib, 1, 1, Cib, Cob];
    bias: [Co/Cob, Cob] or None -> [N, Co/Cob, H, W, Cob].

    Carries the same §14 fusion riders as the window family: ``residual``
    (post-activation add of an output-shaped map) and ``gap`` (per-tile
    f32 partial-sum pool — returns flat ``[N, Co]`` features instead of
    the map).

    Only pointwise geometry is served — stride 1 and VALID/zero padding
    (``ConvSpec.is_pointwise``); anything else belongs to the window
    family and raises here.
    """
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    if w.shape[2] != 1 or w.shape[3] != 1:
        raise ValueError(f"pointwise kernel needs a 1x1 filter, got "
                         f"{w.shape[2]}x{w.shape[3]}")
    # normalize before judging: SAME on a 1x1 filter *is* zero pad, and the
    # dispatcher's is_pointwise predicate (which routes here) says so
    pads = normalize_padding(padding, 1, 1, stride,
                             x.shape[2], x.shape[3])
    if stride != 1 or pads != ((0, 0), (0, 0)):
        raise ValueError(
            f"pointwise fast path serves stride=1, zero-pad only; got "
            f"stride={stride}, padding={padding!r} — route the window "
            f"kernel instead")
    return _pwconv(x, w, bias, residual, activation, hob, wob, machine,
                   interpret, resolve_precision(precision), gap)
