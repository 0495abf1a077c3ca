"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy (DESIGN.md §12): ``direct_conv2d`` resolves its
implementation through the conv dispatch subsystem — per-call ``impl``
override, then the persistent measured table, then the analytical prior —
over the full candidate set (window/streamed Pallas, im2col, lax, jnp
oracle).  On TPU backends the Pallas kernels run compiled; everywhere else
(the CPU) they run in ``interpret=True`` mode, which executes
the same kernel body for correctness validation.  ``impl="jnp"`` pins the
pure-JAX direct formulation in ``repro.core.direct_conv`` — same math,
XLA-scheduled; this is also what the LM models use under ``vmap``/``scan``
where a fixed kernel grid would fight the batching transform.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import layout as L
from repro.core.backend import resolve_interpret, resolve_machine
from repro.core.context import ConvContext, as_context, reject_legacy_kwargs
from repro.core.conv_baselines import (Padding, conv_im2col, conv_lax)
from repro.core.direct_conv import (apply_activation, bias_to_blocked,
                                    direct_conv_nhwc,
                                    direct_conv1d_depthwise)
from repro.core.dispatch import DispatchKey, Impl, get_dispatcher
from .conv1d_depthwise import conv1d_depthwise_blocked_pallas
from .direct_conv2d import direct_conv2d_blocked_pallas

__all__ = ["direct_conv2d", "conv1d_depthwise"]


def direct_conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                  padding: Padding = "VALID", *,
                  bias: Optional[jnp.ndarray] = None,
                  activation: Optional[str] = None,
                  context: Optional[ConvContext] = None,
                  **legacy) -> jnp.ndarray:
    """Direct convolution, NHWC/HWIO interface, zero memory overhead inside.

    x: [N, Hi, Wi, Ci]; w: [Hf, Wf, Ci, Co]; bias: [Co] -> [N, Ho, Wo, Co]

    Padding is stride-aware (TF SAME semantics); bias + activation are fused
    into the kernel epilogue (applied once, on the final Ci block's flush).
    Differentiable on every path (the Pallas kernels carry a custom VJP).

    ``context`` (a :class:`ConvContext`) routes through the dispatch
    subsystem: a forced ``context.impl`` pins one candidate ("window"/
    "stream"/"im2col"/"lax"/"jnp"), otherwise the dispatcher resolves the
    key through its table and prior.  (The loose kwargs are gone; stale
    call sites raise the migration ``TypeError`` naming ``ConvContext``.)
    """
    reject_legacy_kwargs("direct_conv2d", legacy)
    ctx = as_context(context)
    impl, interpret = ctx.impl, ctx.interpret
    if impl is not None and Impl(impl) is Impl.JNP:
        return direct_conv_nhwc(x, w, stride, padding, bias, activation)

    n, hi, wi, ci = x.shape
    co = w.shape[3]
    machine = resolve_machine(ctx.machine)
    disp = ctx.dispatch if ctx.dispatch is not None else get_dispatcher()
    key = DispatchKey.make(n, hi, wi, ci, co, w.shape[0], w.shape[1],
                           stride, padding, ctx.precision, machine, "fwd")
    lay = L.BlockedConvLayout.choose(ci, co)
    dec = disp.decide(key, override=impl,
                      cob=lay.cb_out, cib=lay.cb_in)

    if dec.impl is Impl.JNP:
        return direct_conv_nhwc(x, w, stride, padding, bias, activation)
    if dec.impl in (Impl.IM2COL, Impl.LAX):
        fn = conv_im2col if dec.impl is Impl.IM2COL else conv_lax
        y = fn(x, w, stride, padding)
        if bias is not None:
            y = y + bias
        return apply_activation(y, activation) if activation else y

    # Pallas family: pure layout sandwich — padding is normalized exactly
    # once, inside the kernel wrapper (the blocked map keeps the same H/W),
    # and the bias is reblocked by the shared helper; the dispatcher's
    # per-direction route rides the custom VJP (forward pinned to this
    # decision, dgrad/wgrad resolved independently)
    from repro.core.dispatch import KernelRoute
    kr = disp.kernel_route(key, cob=lay.cb_out, cib=lay.cb_in)
    route = KernelRoute(fwd=dec.impl is Impl.STREAM,
                        dgrad=kr.dgrad, wgrad=kr.wgrad)
    xb = L.nhwc_to_blocked(x, lay.cb_in)
    wb = L.hwio_to_blocked(w, lay.cb_in, lay.cb_out)
    bb = None if bias is None else bias_to_blocked(bias, lay.cb_out)
    yb = direct_conv2d_blocked_pallas(
        xb, wb, bb, stride=stride, padding=padding, activation=activation,
        interpret=interpret, stream=route)
    return L.blocked_to_nhwc(yb)


def conv1d_depthwise(x: jnp.ndarray, w: jnp.ndarray,
                     bias: Optional[jnp.ndarray] = None, *,
                     use_pallas: bool = True, lb: int = 512,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Causal depthwise conv1d.  x: [B, L, D]; w: [K, D] -> [B, L, D]."""
    b, l, d = x.shape
    k = w.shape[0]
    db = L.largest_divisor_leq(d, 128)
    lb = L.largest_divisor_leq(l, lb)
    if not use_pallas or lb < k - 1:
        return direct_conv1d_depthwise(x, w, bias, causal=True)
    xb = L.bld_to_blocked(x, db)
    wb = L.kd_to_blocked(w, db)
    yb = conv1d_depthwise_blocked_pallas(
        xb, wb, lb=lb, interpret=resolve_interpret(interpret))
    y = L.blocked_to_bld(yb)
    if bias is not None:
        y = (y.astype(jnp.float32) + bias.astype(jnp.float32)).astype(y.dtype)
    return y
