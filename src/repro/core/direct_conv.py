"""Zero-memory-overhead direct convolution (paper §3), JAX formulation.

``direct_conv_blocked`` computes convolution on the paper's blocked layout
without ever forming an im2col matrix: for each kernel offset ``(hf, wf)``
it takes a *strided view* of the input map and contracts it against the
``[Cib, Cob]`` weight pencil on the MXU, accumulating into the output tile.
This is Algorithm 3 with the register tile replaced by an MXU tile — the
loop structure (l, n, m, i, k, j) survives as

    offsets (n, m)  ->  unrolled python loop (Hf*Wf small)
    i (Ci blocks)   ->  contraction/scan dimension
    (k, j) tile     ->  the [Ho*Wo, Cob] matmul output

The Pallas kernel in ``repro.kernels.direct_conv2d`` is the hand-tiled
version of exactly this computation; this module is its semantics (and the
path used on non-TPU backends).  Both share the same fused epilogue
(bias + activation applied once, on the final input-channel block) so that
stacked layers chain in the blocked layout with nothing in between —
see DESIGN.md §5.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import layout as L
from .conv_baselines import Padding, normalize_padding, out_size
from .precision import resolve_precision

__all__ = [
    "apply_activation", "pad_blocked", "bias_to_blocked",
    "direct_conv_blocked", "direct_conv_nhwc", "direct_conv1d_depthwise",
]

# erf(x) = x * P(x^2) / Q(x^2) on [-4, 4] (erf(4) is 1 in f32): the
# rational approximation XLA and Eigen use for f32 erf, good to a few ulps.
# Mosaic lowers no erf, so the kernels' epilogue spells it out in
# multiplies, adds and one divide.
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04,
          -2.95459980854025e-03, -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)
_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327


def _horner(coeffs, t):
    acc = jnp.full_like(t, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * t + c
    return acc


def erf_f32(x: jnp.ndarray) -> jnp.ndarray:
    """erf in f32 from elementary ops (see ``_ERF_P``)."""
    x = jnp.clip(x, -4.0, 4.0)
    t = x * x
    return x * _horner(_ERF_P, t) / _horner(_ERF_Q, t)


@jax.custom_jvp
def gelu_erf(x: jnp.ndarray) -> jnp.ndarray:
    """Exact GELU, ``x * Phi(x)`` with the erf form of the normal CDF
    (PyTorch's ``nn.GELU()``, ``jax.nn.gelu(approximate=False)``); its
    derivative ``Phi(x) + x * phi(x)`` is written out, not taken through
    the erf polynomial."""
    return 0.5 * x * (1.0 + erf_f32(x * _SQRT_HALF))


@gelu_erf.defjvp
def _gelu_erf_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    cdf = 0.5 * (1.0 + erf_f32(x * _SQRT_HALF))
    pdf = jnp.exp(-0.5 * x * x) * _INV_SQRT_2PI
    return x * cdf, t * (cdf + x * pdf)


# Epilogue activations fused into the conv (both the jnp oracle and the
# Pallas kernel body call this on the f32 accumulator).  "gelu" is the tanh
# approximation; "gelu_erf" the exact form.
_ACTIVATIONS = {
    None: lambda x: x,
    "linear": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "gelu_erf": gelu_erf,
}


def apply_activation(x: jnp.ndarray, name: Optional[str]) -> jnp.ndarray:
    try:
        return _ACTIVATIONS[name](x)
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; "
                         f"have {sorted(k for k in _ACTIVATIONS if k)}")


def pad_blocked(x: jnp.ndarray, ph, pw) -> jnp.ndarray:
    """Zero-pad the spatial dims of a blocked map [N, C/Cb, H, W, Cb]."""
    if not (any(ph) or any(pw)):
        return x
    return jnp.pad(x, ((0, 0), (0, 0), tuple(ph), tuple(pw), (0, 0)))


def _shifted_window(x: jnp.ndarray, dh: int, dw: int, ho: int, wo: int,
                    stride: int) -> jnp.ndarray:
    """Strided view of blocked input [N, Cib_blocks, Hi, Wi, Cib] at offset."""
    n, cblk, hi, wi, cb = x.shape
    return jax.lax.slice(
        x, (0, 0, dh, dw, 0),
        (n, cblk, dh + (ho - 1) * stride + 1, dw + (wo - 1) * stride + 1, cb),
        (1, 1, stride, stride, 1))


def direct_conv_blocked(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                        padding: Padding = "VALID",
                        bias: Optional[jnp.ndarray] = None,
                        activation: Optional[str] = None,
                        hob: Optional[int] = None,
                        wob: Optional[int] = None,
                        precision=None, groups: int = 1,
                        dilation: int | tuple = 1,
                        residual: Optional[jnp.ndarray] = None,
                        gap: bool = False) -> jnp.ndarray:
    """Direct convolution on blocked layouts, fused bias + activation.

    x: [N, Ci/Cib, Hi, Wi, Cib]      (paper input layout)
    w: [Co/Cob, Cig/Cib, Hf, Wf, Cib, Cob]  (grouped-HWIO kernel layout:
                                      the input extent is per-group,
                                      Cig = Ci // groups; dense is groups=1)
    bias: [Co/Cob, Cob] or None      (blocked channel pencils)
    -> [N, Co/Cob, Ho, Wo, Cob]      (same layout as input: layers chain)

    ``padding`` is stride-aware (TF SAME semantics).  The epilogue
    (bias add + activation) runs on the f32 accumulator before the final
    downcast — identical semantics to the Pallas kernel's fused flush.

    ``hob``/``wob`` mirror the Pallas kernel's spatial-tile knobs so one
    layer config drives either path: this XLA-scheduled formulation is
    tile-agnostic (same math for any tiling), so they are *validated* here
    in the unjitted wrapper — must divide Ho/Wo, exactly the kernel's
    constraint — but never reach the jitted core (identical programs must
    not recompile per tile setting).

    ``precision`` mirrors the Pallas path's mixed-precision policy
    (DESIGN.md §10): operands are cast to ``policy.operand`` here, the
    einsum accumulates f32 (``preferred_element_type``) and the output is
    the operand dtype — so this formulation stays the oracle for the bf16
    kernels too (bias stays master-dtype; the epilogue adds it in f32).

    ``groups``/``dilation`` (DESIGN.md §13): the per-offset contraction
    becomes block-diagonal (each group of output blocks contracts only its
    own group of input blocks) and the strided views start at dilated tap
    offsets.  The depthwise lane layout — full-channel pencils on the maps,
    ``Cib = 1`` on the weight — is recognized and served as a per-lane
    multiply, the same structure as the depthwise Pallas kernel.

    ``residual``/``gap`` mirror the Pallas epilogue riders (DESIGN.md §14):
    ``residual`` is an output-shaped blocked map added *after* the
    activation in f32 with a single downcast; ``gap=True`` returns the
    f32-mean global average pool as flat ``[N, Co]`` features instead of
    the map.
    """
    if precision is not None:
        pol = resolve_precision(precision)
        x = x.astype(pol.op_dtype)
        w = w.astype(pol.op_dtype)
        if residual is not None:
            residual = residual.astype(pol.op_dtype)
    dil = dilation if isinstance(dilation, tuple) else (dilation, dilation)
    hi, wi = x.shape[2], x.shape[3]
    hf, wf = w.shape[2], w.shape[3]
    hf_eff, wf_eff = (hf - 1) * dil[0] + 1, (wf - 1) * dil[1] + 1
    if hob is not None or wob is not None:
        ph, pw = normalize_padding(padding, hf_eff, wf_eff, stride, hi, wi)
        ho = out_size(hi + ph[0] + ph[1], hf_eff, stride)
        wo = out_size(wi + pw[0] + pw[1], wf_eff, stride)
        if hob is not None and (hob < 1 or ho % hob):
            raise ValueError(f"hob={hob} must divide Ho={ho}")
        if wob is not None and (wob < 1 or wo % wob):
            raise ValueError(f"wob={wob} must divide Wo={wo}")
    return _direct_conv_blocked_jit(x, w, stride, padding, bias, activation,
                                    groups, dil, residual, gap)


@partial(jax.jit, static_argnames=("stride", "padding", "activation",
                                   "groups", "dilation", "gap"))
def _direct_conv_blocked_jit(x: jnp.ndarray, w: jnp.ndarray, stride: int,
                             padding: Padding,
                             bias: Optional[jnp.ndarray],
                             activation: Optional[str],
                             groups: int = 1,
                             dilation: tuple = (1, 1),
                             residual: Optional[jnp.ndarray] = None,
                             gap: bool = False) -> jnp.ndarray:
    n, ciblk, hi, wi, cib = x.shape
    coblk, cigblk, hf, wf, cibw, cob = w.shape
    dil_h, dil_w = dilation
    hf_eff, wf_eff = (hf - 1) * dil_h + 1, (wf - 1) * dil_w + 1
    ph, pw = normalize_padding(padding, hf_eff, wf_eff, stride, hi, wi)
    x = pad_blocked(x, ph, pw)
    hi, wi = x.shape[2], x.shape[3]
    ho, wo = out_size(hi, hf_eff, stride), out_size(wi, wf_eff, stride)

    # the depthwise lane layout: full-channel pencils on the feature maps,
    # a collapsed (Cig = 1) input extent on the weight — each lane carries
    # its own group, so the contraction is a per-lane product
    depthwise_lanes = (groups > 1 and cibw == 1 and cib > 1
                       and groups == ciblk * cib)
    if not depthwise_lanes:
        assert cib == cibw and ciblk == cigblk * groups, (x.shape, w.shape,
                                                          groups)

    acc = jnp.zeros((n, coblk, ho, wo, cob), jnp.float32)
    for dh in range(hf):
        for dw in range(wf):
            win = _shifted_window(x, dh * dil_h, dw * dil_w, ho, wo, stride)
            if depthwise_lanes:
                acc = acc + (win.astype(jnp.float32)
                             * w[:, 0, dh, dw, 0].astype(jnp.float32)
                             [None, :, None, None, :])
            elif groups == 1:
                # [N, ci, Ho, Wo, Cib] x [Co, ci, Cib, Cob]
                #   -> [N, Co, Ho, Wo, Cob]
                acc = acc + jnp.einsum(
                    "nchwb,ocbk->nohwk", win, w[:, :, dh, dw],
                    preferred_element_type=jnp.float32)
            else:
                # block-diagonal contraction: group g's output blocks see
                # only group g's input blocks
                wing = win.reshape(n, groups, cigblk, ho, wo, cib)
                wg = w[:, :, dh, dw].reshape(groups, coblk // groups,
                                             cigblk, cibw, cob)
                acc = acc + jnp.einsum(
                    "ngchwb,gocbk->ngohwk", wing, wg,
                    preferred_element_type=jnp.float32,
                ).reshape(n, coblk, ho, wo, cob)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)[None, :, None, None, :]
    acc = apply_activation(acc, activation)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    if gap:
        # mirror the fused kernel's pooling semantics exactly (gap_update):
        # pool the *written* values (downcast to the output dtype first,
        # like the kernel re-reading what epilogue_flush stored), sum flat
        # per channel pencil in f32, divide by the full spatial extent at
        # the end — this is what keeps jnp in EXACT_IMPLS for gap-fused
        # convs, which the serving tier's degraded path relies on
        out = acc.astype(x.dtype)
        flat = out.astype(jnp.float32).reshape(n, coblk, ho * wo, cob)
        pooled = jnp.sum(flat, axis=2) / (ho * wo)
        return pooled.reshape(n, coblk * cob).astype(x.dtype)
    return acc.astype(x.dtype)


def bias_to_blocked(bias: jnp.ndarray, cb_out: int) -> jnp.ndarray:
    """Flat NHWC bias ``[Co] -> [Co/Cb, Cb]`` channel pencils, zero-padding
    Co up to a pencil multiple when needed (matching pad-to-block maps)."""
    co = bias.shape[0]
    if co % cb_out:
        bias = jnp.pad(bias, (0, -co % cb_out))
    return bias.reshape(-1, cb_out)


def direct_conv_nhwc(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                     padding: Padding = "VALID",
                     bias: Optional[jnp.ndarray] = None,
                     activation: Optional[str] = None,
                     pad_to_block: bool = False,
                     lane: int = 128, groups: int = 1,
                     dilation: int | tuple = 1) -> jnp.ndarray:
    """Convenience wrapper: NHWC/HWIO in, NHWC out, via the blocked layouts.

    A pure layout sandwich around :func:`direct_conv_blocked` — permute in,
    convolve, permute out — with **no per-call re-derivation**: padding is
    normalized exactly once (inside ``direct_conv_blocked``, whose blocked
    input keeps the same H/W), the pencils come from the shared
    :func:`layout.choose_pencil`, and ``bias`` is reblocked by
    :func:`bias_to_blocked`.  Because everything around the blocked core is
    a permutation, ``jax.grad`` through this wrapper is the blocked path's
    gradient bit for bit — it is the oracle the custom-VJP tests diff
    against.

    ``pad_to_block=True`` engages the first-class channel-padding layout op
    for non-divisible channel counts (zero-pad in, strip out; the traded
    bytes are ``memory_model.bytes_channel_pad``); dense-only, like the
    packing it wraps.  ``groups``/``dilation`` ride straight down to the
    blocked core (grouped weights are HWIO with the per-group input extent,
    ``w.shape[2] == Ci // groups``).
    """
    hf, wf, cig, co = w.shape
    ci = x.shape[-1]
    if ci != cig * groups:
        raise ValueError(
            f"weight input extent {cig} x groups {groups} != input "
            f"channels {ci}")
    if pad_to_block:
        if groups != 1:
            raise ValueError("pad_to_block supports dense convs only")
        cb_in = L.choose_pencil(ci, lane, pad_to_block=True)
        cb_out = L.choose_pencil(co, lane, pad_to_block=True)
        cb_w = cb_in
    else:
        lay = L.BlockedConvLayout.choose(ci, co, lane, groups=groups)
        cb_in, cb_out, cb_w = lay.cb_in, lay.cb_out, lay.cb_weight
    xb = L.nhwc_to_blocked(x, cb_in, pad_to_block=pad_to_block)
    wb = L.hwio_to_blocked(w, cb_w, cb_out, pad_to_block=pad_to_block)
    bb = None if bias is None else bias_to_blocked(bias, cb_out)
    yb = direct_conv_blocked(xb, wb, stride, padding, bb, activation,
                             groups=groups, dilation=dilation)
    return L.blocked_to_nhwc(yb, co)


@partial(jax.jit, static_argnames=("causal",))
def direct_conv1d_depthwise(x: jnp.ndarray, w: jnp.ndarray,
                            bias: jnp.ndarray | None = None,
                            causal: bool = True) -> jnp.ndarray:
    """Causal depthwise conv1d (the Mamba/Jamba short conv), direct form.

    x: [B, L, D], w: [K, D].  out[b, l, d] = sum_k w[k, d] * x[b, l - K + 1 + k, d].
    Zero memory overhead: K shifted adds, no patch matrix.
    """
    b, l, d = x.shape
    k = w.shape[0]
    if causal:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.pad(x, ((0, 0), ((k - 1) // 2, k - 1 - (k - 1) // 2), (0, 0)))
    acc = jnp.zeros((b, l, d), jnp.float32)
    for i in range(k):
        acc = acc + xp[:, i:i + l, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return acc.astype(x.dtype)
