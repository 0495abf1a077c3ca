"""Pallas TPU kernels: LayerNorm over the channels of a blocked map.

ConvNeXt normalises every pixel over its C channels (``LayerNorm`` with
``data_format="channels_first"`` in the reference code).  On the paper's
layout ``[N, C/Cb, H, W, Cb]`` a pixel's channels are spread over the C/Cb
pencil blocks and the Cb lanes of each, so one grid step holds every block
of a band of rows and reduces over both axes:

  grid = (N, H/th)
  x block   [1, C/Cb, th, W, Cb]
  g, b      [C/Cb, 1, 1, Cb]             f32 affine, whole
  y block   [1, C/Cb, th, W, Cb]         the operand dtype

Statistics are taken in f32: the mean, then the biased variance of the
centred values (two passes over the resident tile, not E[x^2] - E[x]^2),
``rsqrt(var + eps)``.  Only the first ``cb`` lanes of each pencil are
channels.  A compiled launch over a narrow pencil pads its lanes to the
lane tile (``lane_pad``) like the conv families; the padded lanes are
masked out of both sums and written as zeros, then cropped.

The backward recomputes the statistics from ``x`` (no saved mean or
variance: the least HBM traffic is x and dy in, dx out) and writes

  dx = rstd * (dxh - mean_c(dxh) - xhat * mean_c(dxh * xhat)),
  dxh = dy * g,

with the affine gradients ``dg = sum dy * xhat`` and ``db = sum dy`` as f32
pencils per image, accumulated across the row bands of that image and
summed over images outside the kernel.

``layer_norm_blocked`` carries the ``jax.custom_vjp``.  The two launches
are jitted under the names ``layer_norm_blocked_pallas`` and
``layer_norm_bwd_pallas``, which name their events in a device trace.
``layer_norm_blocked_ref`` is the jnp oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_interpret, resolve_machine
from repro.core.blocking import MachineModel
from repro.core.layout import divisors
from repro.core.precision import Precision, resolve_precision
from .conv2d_common import compiler_params, crop_lanes, lane_pad, pad_lanes

__all__ = ["layer_norm_blocked", "layer_norm_blocked_pallas",
           "layer_norm_bwd_pallas", "layer_norm_blocked_ref"]

# f32 bytes of one resident x tile: the kernels hold a few f32 temporaries
# of this size (x, xhat, dy, dxh), far inside the scoped VMEM
_TILE_BYTES = 2 * 2 ** 20


def _rows(k: int, h: int, w: int, cb: int) -> int:
    """Rows per band: the most that keep one f32 tile in ``_TILE_BYTES``
    (lanes and sublanes counted as the tile pads them), at least 1."""
    row = k * (-(-w // 8) * 8) * (-(-cb // 128) * 128) * 4
    best = 1
    for d in divisors(h):
        if d * row <= _TILE_BYTES:
            best = d
    return best


def _normalise(x, cb: int, c: int, eps: float):
    """-> (xhat, rstd) of an f32 tile ``[K, th, W, Cbp]`` whose first
    ``cb`` lanes of each pencil are channels; xhat is 0 on the others."""
    mask = None
    if x.shape[-1] != cb:
        mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) < cb
        x = jnp.where(mask, x, 0.0)
    inv_c = 1.0 / c
    mean = jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=-1,
                   keepdims=True) * inv_c
    xc = x - mean
    if mask is not None:
        xc = jnp.where(mask, xc, 0.0)
    var = jnp.sum(jnp.sum(xc * xc, axis=0, keepdims=True), axis=-1,
                  keepdims=True) * inv_c
    rstd = jax.lax.rsqrt(var + eps)
    return xc * rstd, rstd


def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, *, cb, c, eps):
    xhat, _ = _normalise(x_ref[0].astype(jnp.float32), cb, c, eps)
    o_ref[0] = (xhat * g_ref[...] + b_ref[...]).astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, db_ref, *, cb, c,
                   eps):
    xhat, rstd = _normalise(x_ref[0].astype(jnp.float32), cb, c, eps)
    dy = dy_ref[0].astype(jnp.float32)
    dxh = dy * g_ref[...]
    inv_c = 1.0 / c
    m1 = jnp.sum(jnp.sum(dxh, axis=0, keepdims=True), axis=-1,
                 keepdims=True) * inv_c
    m2 = jnp.sum(jnp.sum(dxh * xhat, axis=0, keepdims=True), axis=-1,
                 keepdims=True) * inv_c
    dx = rstd * (dxh - m1 - xhat * m2)
    if dx.shape[-1] != cb:
        lane = jax.lax.broadcasted_iota(jnp.int32, dx.shape, dx.ndim - 1)
        dx = jnp.where(lane < cb, dx, 0.0)
    dx_ref[0] = dx.astype(dx_ref.dtype)

    def pencil_sum(t):                     # [K, th, W, Cbp] -> [K, 1, Cbp]
        return jnp.sum(jnp.sum(t, axis=2, keepdims=True), axis=1)

    dg, db = pencil_sum(dy * xhat), pencil_sum(dy)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dg_ref[0] = dg
        db_ref[0] = db

    @pl.when(pl.program_id(1) > 0)
    def _acc():
        dg_ref[0] += dg
        db_ref[0] += db


def _map_spec(k, th, w, cbp):
    return pl.BlockSpec((1, k, th, w, cbp), lambda n, t: (n, 0, t, 0, 0))


def _affine_spec(k, cbp):
    return pl.BlockSpec((k, 1, 1, cbp), lambda n, t: (0, 0, 0, 0))


def _affine(p, pad):
    """``[K, Cb]`` f32 pencils -> ``[K, 1, 1, Cb + pad]``."""
    p = pad_lanes(p.astype(jnp.float32), pad)
    return p.reshape(p.shape[0], 1, 1, p.shape[1])


@partial(jax.jit, static_argnames=("lanes", "eps", "machine", "interpret"))
def layer_norm_blocked_pallas(x, gamma, beta, *, lanes: int, eps: float,
                              machine: MachineModel, interpret: bool):
    """One forward launch: ``x`` ``[N, K, H, W, Cb]`` whose first ``lanes``
    lanes of each pencil are channels -> y, same shape and dtype;
    ``gamma``/``beta`` ``[K, Cb]``."""
    n, k, h, w, cb = x.shape
    (pad,) = lane_pad(machine, interpret, cb)
    cbp = cb + pad
    th = _rows(k, h, w, cbp)
    y = pl.pallas_call(
        partial(_ln_fwd_kernel, cb=lanes, c=k * lanes, eps=eps),
        grid=(n, h // th),
        in_specs=[_map_spec(k, th, w, cbp), _affine_spec(k, cbp),
                  _affine_spec(k, cbp)],
        out_specs=_map_spec(k, th, w, cbp),
        out_shape=jax.ShapeDtypeStruct((n, k, h, w, cbp), x.dtype),
        compiler_params=compiler_params(machine, ("parallel", "parallel")),
        interpret=interpret,
    )(pad_lanes(x, pad), _affine(gamma, pad), _affine(beta, pad))
    return crop_lanes(y, cb, False, k)


@partial(jax.jit, static_argnames=("lanes", "eps", "machine", "interpret"))
def layer_norm_bwd_pallas(x, dy, gamma, *, lanes: int, eps: float,
                          machine: MachineModel, interpret: bool):
    """One backward launch -> (dx in ``x``'s dtype, dgamma, dbeta as f32
    ``[K, Cb]``)."""
    n, k, h, w, cb = x.shape
    (pad,) = lane_pad(machine, interpret, cb)
    cbp = cb + pad
    th = _rows(k, h, w, cbp)
    pen = pl.BlockSpec((1, k, 1, cbp), lambda n, t: (n, 0, 0, 0))
    pen_shape = jax.ShapeDtypeStruct((n, k, 1, cbp), jnp.float32)
    dx, dg, db = pl.pallas_call(
        partial(_ln_bwd_kernel, cb=lanes, c=k * lanes, eps=eps),
        grid=(n, h // th),
        in_specs=[_map_spec(k, th, w, cbp), _map_spec(k, th, w, cbp),
                  _affine_spec(k, cbp)],
        out_specs=[_map_spec(k, th, w, cbp), pen, pen],
        out_shape=[jax.ShapeDtypeStruct((n, k, h, w, cbp), x.dtype),
                   pen_shape, pen_shape],
        compiler_params=compiler_params(machine, ("parallel", "arbitrary")),
        interpret=interpret,
    )(pad_lanes(x, pad), pad_lanes(dy.astype(x.dtype), pad),
      _affine(gamma, pad))
    return (crop_lanes(dx, cb, False, k), dg.sum(0)[:, 0, :cb],
            db.sum(0)[:, 0, :cb])


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, gamma, beta, eps, machine, interpret):
    return layer_norm_blocked_pallas(x, gamma, beta, lanes=x.shape[4],
                                     eps=eps, machine=machine,
                                     interpret=interpret)


def _ln_fwd(x, gamma, beta, eps, machine, interpret):
    return _ln(x, gamma, beta, eps, machine, interpret), (x, gamma, beta)


def _ln_bwd(eps, machine, interpret, res, dy):
    x, gamma, beta = res
    dx, dg, db = layer_norm_bwd_pallas(x, dy, gamma, lanes=x.shape[4],
                                       eps=eps, machine=machine,
                                       interpret=interpret)
    return dx, dg.astype(gamma.dtype), db.astype(beta.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_blocked(x, gamma, beta, *, eps: float = 1e-6,
                       precision: Precision | str | None = None,
                       machine: Optional[MachineModel] = None,
                       interpret: Optional[bool] = None):
    """LayerNorm of each pixel of a blocked map over its C channels, every
    lane of every pencil; differentiable.

    x: [N, C/Cb, H, W, Cb] -> the same shape in the policy's operand dtype
    (x's own dtype without a policy); gamma, beta: [C/Cb, Cb] f32 masters.
    """
    if precision is not None:
        x = x.astype(resolve_precision(precision).op_dtype)
    return _ln(x, gamma, beta, float(eps), resolve_machine(machine),
               resolve_interpret(interpret))


def layer_norm_blocked_ref(x, gamma, beta, *, lanes: Optional[int] = None,
                           eps: float = 1e-6):
    """The jnp oracle of :func:`layer_norm_blocked` (no policy cast)."""
    lanes = x.shape[4] if lanes is None else lanes
    xhat = jax.vmap(lambda t: _normalise(t, lanes, x.shape[1] * lanes,
                                         eps)[0])(x.astype(jnp.float32))
    g = gamma.astype(jnp.float32)[None, :, None, None, :]
    b = beta.astype(jnp.float32)[None, :, None, None, :]
    return (xhat * g + b).astype(x.dtype)
