"""Pallas TPU kernels: zero-memory-overhead direct convolution (paper Alg. 3)
— a *family* of three kernels sharing one grid machinery (DESIGN.md §2–§5,
§7, §9):

  forward   out = conv(x, w) + bias, activation     (the paper's kernel)
  dgrad     dx  = conv(dilate(dŷ), mirror(w))        (input gradient)
  wgrad     dw  = Σ_tiles  x_windowᵀ @ dŷ_tile       (weight gradient)

All three are parameterized by the same ``core.blocking`` output and built
from ``kernels.conv2d_common``: the halo'd element-offset input window,
the strided ``tap_windows`` VMEM views (the im2col rows that are never
materialized), the reduction-axis init/flush guards and the fused epilogue.

Forward grid (exactly the paper's schedule):

  grid = (N, Co/Cob, Ho/Hob, Wo/Wob, Ci/Cib)   # j', spatial tile, i' (red.)
  x block   [1, 1, Hib, Wib, Cib]     # halo'd patch: Hib=(Hob-1)*stride+Hf
  w block   [1, 1, Hf, Wf, Cib, Cob]  # paper kernel layout, VMEM
  b block   [1, Cob]                  # bias pencil (only when bias given)
  out block [1, 1, Hob, Wob, Cob]     # the "register" tile (lane dim = Cob)

dgrad is the same schedule applied to the *transposed* problem: the grid
walks input-gradient tiles ``(N, Ci/Cib, E_h/Hob, E_w/Wob, Co/Cob)`` with
the cotangent (stride-dilated, ``Hf-1``-halo-padded) as the windowed
operand, the filter taps mirrored (``w[Hf-1-dh, Wf-1-dw]``) and the pencil
contraction flipped to ``Cob`` (``choose_dgrad_blocking`` swaps the roles).

wgrad flips which axes are the reduction: the grid is
``(Co/Cob, Ci/Cib, N, Ho/Hob, Wo/Wob)`` with the *last three* axes reduced
into one resident ``[Hf, Wf, Cib, Cob]`` f32 accumulator per weight block —
each step contracts a strided x window against the cotangent tile over the
``Hob*Wob`` spatial positions (``choose_wgrad_blocking`` sizes the tile
against the accumulator-widened VMEM inequality).

``direct_conv2d_blocked_pallas`` carries a ``jax.custom_vjp`` wired to the
backward kernels, so ``jax.grad`` flows *through the Pallas path*: training
no longer detours through the XLA-scheduled jnp formulation.  The VJP's
forward saves the pre-activation tile as its epilogue residual (computed by
the same fused kernel with the activation deferred), so the activation and
bias cotangents are exact — ``dŷ_pre = dŷ * act'(z)``, ``db = Σ_{N,H,W}
dŷ_pre`` — and both backward kernels consume ``dŷ_pre``.

Every entry point takes a ``precision`` policy (``core.precision.Precision``,
DESIGN.md §10): operands are down-cast to ``policy.operand`` once on entry,
every contraction accumulates in f32 (``preferred_element_type`` + the f32
scratch tiles — bf16 runs are never bf16-naive sums), residuals are stored at
``policy.residual``, and cotangents are up-cast exactly once on VJP exit
(the weight gradient leaves the wgrad kernel in f32 and reaches f32 master
params without a bf16 round-trip).  bf16 operands also halve the VMEM
inequality, so the blocking model admits larger tiles (the itemsize is taken
from the actual operand arrays — the policy and the fit can't drift).

Every entry point also takes a ``stream`` knob (DESIGN.md §11–§12): each of
the three kernels has a streamed halo-DMA twin in ``kernels/conv2d_stream.py``
(input kept in HBM, double-buffered ``make_async_copy`` ring of row-strips,
singly-resident weight tile), and the wrappers here route between the two.
The slot accepts ``True``/``False`` (force all three directions onto one
family — the legacy contract), ``None`` (resolve per launch), or a
``core.dispatch.KernelRoute`` (per-direction resolution, what
``ConvDispatcher`` hands down).  Resolution is a *pre-launch probe* of the
same blocking model the kernel fits against (``core.dispatch.route_pallas``)
— the old launch-and-catch-``VmemMisfitError`` chain, moved out of these
wrappers and into the dispatch subsystem — so what used to be the family's
one hard failure (deep pinned pencils misfitting at ``hob = wob = 1``) is a
served configuration, and a forced path (``stream=False``/``True``) still
lets its own misfit propagate.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret, resolve_machine
from repro.core.blocking import (MachineModel, choose_blocking,
                                 choose_dgrad_blocking,
                                 choose_wgrad_blocking, dgrad_extents)
from repro.core.conv_baselines import Padding
from repro.core.convspec import ConvSpec
from repro.core.dispatch import KernelRoute, route_pallas, stream_flag
from repro.core.direct_conv import apply_activation, pad_blocked
from repro.core.precision import F32, Precision, resolve_precision
from repro.utils.faults import inject as _inject_fault
from .conv2d_common import (bias_spec, compiler_params, cotangent_prologue,
                            crop_lanes, epilogue_flush, first_step,
                            forward_semantics, gap_spec, gap_update,
                            halo_dims, halo_window_spec, lane_pad, last_step,
                            pad_lanes, pencils, strided_source, tap_windows,
                            tile_spec, unpencil, weight_spec, wgrad_semantics,
                            window_steps)
from .conv2d_stream import stream_dgrad, stream_forward, stream_wgrad

__all__ = ["direct_conv2d_blocked_pallas", "direct_conv2d_dgrad_pallas",
           "direct_conv2d_wgrad_pallas"]


# ---------------------------------------------------------------------------
# kernel bodies — each is only its contraction; the grid/Spec/epilogue
# machinery is shared (kernels.conv2d_common)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, *rest, hf, wf, hob, wob, stride, activation,
                has_bias, has_residual, has_gap, hw, dilation=(1, 1)):
    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    r_ref = rest.pop(0) if has_residual else None
    o_ref = rest.pop(0)
    g_ref = rest.pop(0) if has_gap else None
    acc_ref = rest.pop(0)
    gacc_ref = rest.pop(0) if has_gap else None

    @pl.when(first_step((4,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def taps(src, dtype, lead):
        for (dh, dw), win in tap_windows(src, hf, wf, hob, wob, stride,
                                         dilation, dtype, lead):
            acc_ref[...] += jnp.dot(win, w_ref[0, 0, dh, dw],
                                    preferred_element_type=jnp.float32)

    strided_source(x_ref, stride, taps)

    # GAP guards hoisted out of the flush conditional (program_id may not be
    # issued inside a pl.when body)
    gap_first = first_step((2, 3)) if has_gap else None
    gap_last = last_step((2, 3)) if has_gap else None

    @pl.when(last_step((4,)))
    def _flush():
        tile = epilogue_flush(o_ref, acc_ref[...], hob, wob, b_ref,
                              activation, r_ref)
        # GAP rider: the spatial-tile axes (2, 3) sequence all flushes of one
        # (n, co) pair, so the f32 partial-sum scratch re-inits on the first
        # tile and the pooled pencil is written exactly once, on the last.
        if has_gap:
            gap_update(g_ref, gacc_ref, tile, hw, gap_first, gap_last)


def _dgrad_kernel(dy_ref, *rest, hf, wf, hob, wob, has_z, activation,
                  dilation=(1, 1)):
    """Transposed-window input gradient: mirrored taps over the (already
    stride-dilated + halo-padded) cotangent, contracting the Cob pencil.
    Windows slide by 1 — the forward stride lives in the cotangent's
    dilation; a forward *filter* dilation keeps striding the taps.

    With ``has_z`` the saved pre-activation rides a second halo window
    (dilated/padded identically to the cotangent) and the activation
    cotangent ``dz = g * act'(z)`` is formed on the whole patch before the
    taps slide — elementwise, so it commutes with the windowing, and the
    dilation's structural zeros stay zero (``0 * act'`` is 0)."""
    rest = list(rest)
    z_ref = rest.pop(0) if has_z else None
    w_ref, o_ref, acc_ref = rest

    @pl.when(first_step((4,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    patch, lead = dy_ref, (0, 0)
    if z_ref is not None:
        patch, lead = cotangent_prologue(dy_ref[0, 0], z_ref[0, 0],
                                         activation), ()
    for (dh, dw), win in tap_windows(patch, hf, wf, hob, wob, 1,
                                     dilation, lead=lead):
        # [Hob*Wob, Cob] x [Cib, Cob] -> [Hob*Wob, Cib]  (contract lanes)
        acc_ref[...] += jax.lax.dot_general(
            win, w_ref[0, 0, hf - 1 - dh, wf - 1 - dw],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last_step((4,)))
    def _flush():
        epilogue_flush(o_ref, acc_ref[...], hob, wob)


def _wgrad_kernel(x_ref, dy_ref, *rest, hf, wf, hob, wob, stride, has_z,
                  activation, with_db, dilation=(1, 1)):
    """Per-tile accumulating weight gradient: the whole [Hf, Wf, Cib, Cob]
    block stays resident while the (N, Ho/Hob, Wo/Wob) reduction axes walk;
    each step contracts the Hob*Wob spatial positions.

    With ``has_z`` the cotangent tile is replaced by ``dz = g * act'(z)`` on
    load; with ``with_db`` the bias cotangent ``db = Σ dz`` accumulates in a
    [1, Cob] f32 scratch — only on the ``ci == 0`` pass (every (n, th, tw)
    tile appears once per ci, summing each pass would overcount) — and is
    flushed once per Co block."""
    rest = list(rest)
    z_ref = rest.pop(0) if has_z else None
    o_ref = rest.pop(0)
    db_ref = rest.pop(0) if with_db else None
    acc_ref = rest.pop(0)
    dbacc_ref = rest.pop(0) if with_db else None

    @pl.when(first_step((2, 3, 4)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])
    if z_ref is not None:
        z = z_ref[0, 0].reshape(hob * wob, dy_ref.shape[-1])
        dy = cotangent_prologue(dy, z, activation)

    if with_db:
        # guard hoisted: program_id may not be issued inside a pl.when body
        db_first = first_step((2, 3, 4))

        @pl.when(pl.program_id(1) == 0)
        def _db_accum():
            part = jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)
            dbacc_ref[...] = jnp.where(db_first, part,
                                       dbacc_ref[...] + part)

        @pl.when(last_step((1, 2, 3, 4)))
        def _db_flush():
            db_ref[0] = dbacc_ref[...].astype(db_ref.dtype)

    def taps(src, dtype, lead):
        for (dh, dw), win in tap_windows(src, hf, wf, hob, wob, stride,
                                         dilation, dtype, lead):
            # [Hob*Wob, Cib] x [Hob*Wob, Cob] -> [Cib, Cob]  (contract
            # positions)
            acc_ref[dh, dw] = acc_ref[dh, dw] + jax.lax.dot_general(
                win, dy, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    strided_source(x_ref, stride, taps)

    @pl.when(last_step((2, 3, 4)))
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# forward launch (operates on an already-padded input — always VALID)
# ---------------------------------------------------------------------------

def _resolve_stream(stream, hso: Optional[int],
                    direction: str) -> Optional[bool]:
    """Normalize the routing knob to this direction's flag: a
    ``KernelRoute`` contributes its per-direction field, and an explicit
    strip height implies the streamed path (``hso`` has no meaning on the
    window path)."""
    flag = stream_flag(stream, direction)
    if hso is not None:
        if flag is False:
            raise ValueError("hso= is the streamed variant's strip height; "
                             "it cannot combine with stream=False")
        return True
    return flag


def _forward_impl(xp: jnp.ndarray, w: jnp.ndarray, bias, stride: int,
                  activation, hob, wob, machine: MachineModel,
                  interpret: bool, stream=None,
                  hso: Optional[int] = None, groups: int = 1,
                  dilation=(1, 1), residual=None, gap: bool = False):
    """Route one forward launch.  An explicit flag (``stream`` bool, a
    ``KernelRoute.fwd``, or ``hso``) pins the variant — a forced path's
    misfit propagates; with ``None`` the dispatch probe
    (``route_pallas``) asks the window inequality first and degrades to
    the streamed family when it misfits — the old ``hob = wob = 1``
    hard-raise, served.  The streamed family is dense-only: grouped or
    dilated geometry pins the window path (and rejects a forced
    ``stream=True``)."""
    _inject_fault("kernel.launch")      # fires at trace time (jit caller)
    flag = _resolve_stream(stream, hso, "fwd")
    dense = groups == 1 and tuple(dilation) == (1, 1)
    if flag and not dense:
        raise ValueError(
            f"the streamed halo-DMA kernels are dense-only; got "
            f"groups={groups}, dilation={tuple(dilation)}")
    if flag is None:
        if not dense:
            flag = False
        else:
            n, ciblk, hi, wi, cib = xp.shape
            coblk, _, hf, wf, _, cob = w.shape
            flag = route_pallas("fwd", n=n, hi=hi, wi=wi, ci=ciblk * cib,
                                co=coblk * cob, hf=hf, wf=wf, stride=stride,
                                machine=machine, dtype=xp.dtype, cob=cob,
                                cib=cib, hob=hob, wob=wob)
    if flag:
        return stream_forward(xp, w, bias, stride, activation, hob, wob,
                              hso, machine, interpret, residual=residual,
                              gap=gap)
    return _forward_windowed(xp, w, bias, stride, activation, hob, wob,
                             machine, interpret, groups, dilation,
                             residual, gap)


def _forward_windowed(xp: jnp.ndarray, w: jnp.ndarray, bias, stride: int,
                      activation, hob, wob, machine: MachineModel,
                      interpret: bool, groups: int = 1,
                      dilation=(1, 1), residual=None, gap: bool = False):
    n, ciblk, hi, wi, cib = xp.shape
    coblk, cigblk, hf, wf, cib2, cob = w.shape
    # grouped-HWIO weights: the blocked input extent is the *per-group*
    # channel count; dense is the groups=1 special case (cigblk == ciblk)
    assert cib == cib2 and ciblk == cigblk * groups and coblk % groups == 0, \
        (xp.shape, w.shape, groups)
    dil_h, dil_w = dilation
    ho = (hi - ((hf - 1) * dil_h + 1)) // stride + 1
    wo = (wi - ((wf - 1) * dil_w + 1)) // stride + 1

    # pin cob/cib to this call's actual pencil sizes (and any explicit
    # hob/wob) so the VMEM fit is evaluated against the blocks the kernel
    # will really hold; choose_blocking also validates pinned tiles (must
    # divide Ho/Wo, must fit), so misuse gets the model's clear error here
    # instead of an opaque VMEM allocation failure at kernel launch
    blk = choose_blocking(hi, wi, ciblk * cib, coblk * cob, hf, wf,
                          stride, machine=machine, cob=cob, cib=cib,
                          hob=hob, wob=wob,
                          in_dtype_bytes=xp.dtype.itemsize,
                          groups=groups, dilation=dilation,
                          fused_residual=residual is not None,
                          fused_gap=gap)
    hob, wob = blk.hob, blk.wob
    hib, wib = halo_dims(hob, wob, hf, wf, stride, dilation)
    cogblk = coblk // groups

    has_bias = bias is not None
    has_residual = residual is not None
    operands = [xp, w]
    in_specs = [
        # block-diagonal reach into x: output block `co` belongs to group
        # co // cogblk, whose input blocks start at (co // cogblk) * cigblk.
        # groups=1 degenerates to plain `ci` — dense launches are untouched.
        halo_window_spec(hib, wib, cib,
                         *window_steps(ho, wo, hob, wob, stride),
                         lambda b, co, th, tw, ci:
                         (b, (co // cogblk) * cigblk + ci, th, tw)),
        weight_spec(hf, wf, cib, cob,
                    lambda b, co, th, tw, ci: (co, ci)),
    ]
    if has_bias:
        operands.append(pencils(bias))
        in_specs.append(bias_spec(cob, lambda b, co, th, tw, ci: (co,)))
    if has_residual:
        assert residual.shape == (n, coblk, ho, wo, cob), \
            (residual.shape, (n, coblk, ho, wo, cob))
        operands.append(residual)
        in_specs.append(tile_spec(hob, wob, cob,
                                  lambda b, co, th, tw, ci: (b, co, th, tw)))

    out_specs = tile_spec(hob, wob, cob,
                          lambda b, co, th, tw, ci: (b, co, th, tw))
    out_shape = jax.ShapeDtypeStruct((n, coblk, ho, wo, cob), xp.dtype)
    scratch = [pltpu.VMEM((hob * wob, cob), jnp.float32)]
    if gap:
        out_specs = [out_specs,
                     gap_spec(cob, lambda b, co, th, tw, ci: (b, co))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n, coblk, 1, cob), xp.dtype)]
        scratch.append(pltpu.VMEM((1, cob), jnp.float32))

    grid = (n, coblk, ho // hob, wo // wob, cigblk)
    return unpencil(pl.pallas_call(
        partial(_fwd_kernel, hf=hf, wf=wf, hob=hob, wob=wob, stride=stride,
                activation=activation, has_bias=has_bias,
                has_residual=has_residual, has_gap=gap, hw=ho * wo,
                dilation=dilation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(machine, forward_semantics(gap)),
        interpret=interpret,
    )(*operands), gap)


# ---------------------------------------------------------------------------
# backward kernel launches
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("stride", "hob", "wob", "machine",
                                   "interpret", "stream", "hso", "groups",
                                   "dilation", "activation"))
def direct_conv2d_dgrad_pallas(dy: jnp.ndarray, w: jnp.ndarray,
                               stride: int = 1,
                               hob: Optional[int] = None,
                               wob: Optional[int] = None,
                               machine: Optional[MachineModel] = None,
                               interpret: Optional[bool] = None,
                               stream: Optional[bool] = None,
                               hso: Optional[int] = None,
                               groups: int = 1,
                               dilation=(1, 1),
                               z: Optional[jnp.ndarray] = None,
                               activation: Optional[str] = None
                               ) -> jnp.ndarray:
    """Input gradient of the VALID blocked conv, as a direct convolution.

    dy: [N, Co/Cob, Ho, Wo, Cob] cotangent; w: the forward's blocked weights
    -> [N, Ci/Cib, Eh, Ew, Cib] gradient w.r.t. the *padded* forward input,
    truncated at the touched extents ``E = (out-1)*stride + filter``
    (``blocking.dgrad_extents``) — rows/cols of the padded input beyond E
    are never read by the forward, so their gradient is zero and the caller
    (the custom VJP) pads/crops to the original input shape.

    The stride is folded into a spatial dilation of the cotangent (s-1 zeros
    between elements) so the kernel itself always slides by 1; the ``Hf-1``
    halo pad turns the correlation into the full (transposed) convolution.
    The dilated copy is the one backward-only memory concession — accounted
    in ``memory_model``-style terms in DESIGN.md §9.

    ``stream`` routes like the forward: None probes the transposed window
    inequality and falls to the streamed kernel when it misfits, True
    forces it (``hso`` stripes the dgrad extents), False pins the window
    path (its misfit propagates), and a ``KernelRoute`` contributes its
    ``dgrad`` field.  Grouped/dilated geometry pins the window path (the
    streamed family is dense-only).

    ``z``/``activation`` fuse the activation cotangent as a prologue: ``dy``
    is the *raw* incoming cotangent and the kernel forms ``dz = dy *
    act'(z)`` on tile load (``z`` is the saved pre-activation, ``dy``'s
    shape).  The streamed route stays unfused — the prologue is applied
    outside before the ring launch.
    """
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    _inject_fault("kernel.launch")
    flag = _resolve_stream(stream, hso, "dgrad")
    dense = groups == 1 and tuple(dilation) == (1, 1)
    if flag and not dense:
        raise ValueError(
            f"the streamed halo-DMA kernels are dense-only; got "
            f"groups={groups}, dilation={tuple(dilation)}")
    if flag is None:
        if not dense:
            flag = False
        else:
            n, coblk, ho, wo, cob = dy.shape
            _, ciblk, hf, wf, cib, _ = w.shape
            flag = route_pallas("dgrad", n=n, hi=(ho - 1) * stride + hf,
                                wi=(wo - 1) * stride + wf, ci=ciblk * cib,
                                co=coblk * cob, hf=hf, wf=wf, stride=stride,
                                machine=machine, dtype=dy.dtype, cob=cob,
                                cib=cib, hob=hob, wob=wob)
    if flag:
        if z is not None:
            dy = cotangent_prologue(dy, z, activation)
        return stream_dgrad(dy, w, stride, hob, wob, hso, machine, interpret)
    return _dgrad_windowed(dy, w, stride, hob, wob, machine, interpret,
                           groups, dilation, z, activation)


def _dgrad_windowed(dy: jnp.ndarray, w: jnp.ndarray, stride: int,
                    hob: Optional[int], wob: Optional[int],
                    machine: MachineModel, interpret: bool,
                    groups: int = 1, dilation=(1, 1),
                    z=None, activation=None) -> jnp.ndarray:
    n, coblk, ho, wo, cob = dy.shape
    coblk2, cigblk, hf, wf, cib, cob2 = w.shape
    assert (coblk, cob) == (coblk2, cob2), (dy.shape, w.shape)
    assert coblk % groups == 0, (w.shape, groups)
    dil_h, dil_w = dilation
    ciblk = cigblk * groups
    cogblk = coblk // groups

    def _dilate_pad(t):
        if stride > 1:
            td = jnp.zeros((n, coblk, (ho - 1) * stride + 1,
                            (wo - 1) * stride + 1, cob), t.dtype)
            td = td.at[:, :, ::stride, ::stride, :].set(t)
        else:
            td = t
        # the full-conv halo pad spans the *effective* (dilated) filter reach
        return pad_blocked(td, ((hf - 1) * dil_h, (hf - 1) * dil_h),
                           ((wf - 1) * dil_w, (wf - 1) * dil_w))

    dyp = _dilate_pad(dy)
    # z rides a second identically-dilated window — the prologue is
    # elementwise, so dilating before it only multiplies act'(z) by the
    # structural zeros already in the dilated cotangent
    zp = None if z is None else _dilate_pad(z)

    eh, ew = dgrad_extents(ho, wo, hf, wf, stride, dilation)
    blk = choose_dgrad_blocking(ho, wo, ciblk * cib, coblk * cob, hf, wf,
                                stride, machine=machine, cib=cib, cob=cob,
                                hob=hob, wob=wob,
                                in_dtype_bytes=dy.dtype.itemsize,
                                groups=groups, dilation=dilation,
                                fused_prologue=z is not None)
    hob, wob = blk.hob, blk.wob
    # windows slide by 1 (stride lives in the cotangent's dilation); filter
    # dilation still strides the taps
    hib, wib = halo_dims(hob, wob, hf, wf, 1, dilation)

    # input block `ci` belongs to group ci // cigblk; its group's
    # cotangent blocks start at (ci // cigblk) * cogblk and the
    # matching weight block row is the same offset + the reduction id
    cot_window = lambda: halo_window_spec(
        hib, wib, cob, *window_steps(eh, ew, hob, wob, 1),
        lambda b, ci, th, tw, co: (b, (ci // cigblk) * cogblk + co, th, tw))
    operands = [dyp]
    in_specs = [cot_window()]
    if zp is not None:
        operands.append(zp)
        in_specs.append(cot_window())
    operands.append(w)
    in_specs.append(weight_spec(hf, wf, cib, cob,
                                lambda b, ci, th, tw, co:
                                ((ci // cigblk) * cogblk + co, ci % cigblk)))

    grid = (n, ciblk, eh // hob, ew // wob, cogblk)
    return pl.pallas_call(
        partial(_dgrad_kernel, hf=hf, wf=wf, hob=hob, wob=wob,
                has_z=zp is not None, activation=activation,
                dilation=dilation),
        grid=grid,
        in_specs=in_specs,
        out_specs=tile_spec(hob, wob, cib,
                            lambda b, ci, th, tw, co: (b, ci, th, tw)),
        out_shape=jax.ShapeDtypeStruct((n, ciblk, eh, ew, cib), dy.dtype),
        scratch_shapes=[pltpu.VMEM((hob * wob, cib), jnp.float32)],
        compiler_params=compiler_params(
            machine, ("parallel",) * 4 + ("arbitrary",)),
        interpret=interpret,
    )(*operands)


@partial(jax.jit, static_argnames=("hf", "wf", "stride", "hob", "wob",
                                   "machine", "interpret", "out_dtype",
                                   "stream", "hso", "groups", "dilation",
                                   "activation", "with_db"))
def direct_conv2d_wgrad_pallas(xp: jnp.ndarray, dy: jnp.ndarray,
                               hf: int, wf: int, stride: int = 1,
                               hob: Optional[int] = None,
                               wob: Optional[int] = None,
                               machine: Optional[MachineModel] = None,
                               interpret: Optional[bool] = None,
                               out_dtype=None,
                               stream: Optional[bool] = None,
                               hso: Optional[int] = None,
                               groups: int = 1,
                               dilation=(1, 1),
                               z: Optional[jnp.ndarray] = None,
                               activation: Optional[str] = None,
                               with_db: bool = False):
    """Weight gradient of the VALID blocked conv, accumulated per tile.

    xp: [N, Ci/Cib, Hi, Wi, Cib] the forward's *padded* input;
    dy: [N, Co/Cob, Ho, Wo, Cob] cotangent
    -> [Co/Cob, Ci/Cib, Hf, Wf, Cib, Cob] in the paper's kernel layout.

    The (N, Ho/Hob, Wo/Wob) grid axes are the reduction: each (Co, Ci)
    block's [Hf, Wf, Cib, Cob] accumulator stays resident in f32 VMEM
    scratch across all their steps and is stored exactly once.

    ``stream`` routes like the forward: None probes the accumulator-widened
    window inequality and falls to the streamed wgrad (both operands
    ringed, the accumulator flushed by manual DMA) when it misfits, True
    forces it, False pins the window path, and a ``KernelRoute``
    contributes its ``wgrad`` field.

    ``z``/``activation`` fuse the activation cotangent on tile load (``dy``
    then being the *raw* cotangent, ``z`` the saved pre-activation, same
    shape); ``with_db`` additionally accumulates ``db = Σ dz`` in a
    flush-once f32 scratch and makes the return a ``(dw, db)`` pair with
    ``db`` in f32 ``[Co/Cob, Cob]`` pencils.  The streamed route stays
    unfused: dz is formed outside and db summed by XLA.
    """
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    _inject_fault("kernel.launch")
    flag = _resolve_stream(stream, hso, "wgrad")
    dense = groups == 1 and tuple(dilation) == (1, 1)
    if flag and not dense:
        raise ValueError(
            f"the streamed halo-DMA kernels are dense-only; got "
            f"groups={groups}, dilation={tuple(dilation)}")
    if flag is None:
        if not dense:
            flag = False
        else:
            n, coblk, ho, wo, cob = dy.shape
            _, ciblk, _, _, cib = xp.shape
            flag = route_pallas("wgrad", n=n, hi=(ho - 1) * stride + hf,
                                wi=(wo - 1) * stride + wf, ci=ciblk * cib,
                                co=coblk * cob, hf=hf, wf=wf, stride=stride,
                                machine=machine, dtype=xp.dtype, cob=cob,
                                cib=cib, hob=hob, wob=wob)
    if flag:
        if z is not None:
            dy = cotangent_prologue(dy, z, activation)
        dw = stream_wgrad(xp, dy, hf, wf, stride, wob, hso, machine,
                          interpret, out_dtype)
        if with_db:
            db = dy.astype(jnp.float32).sum(axis=(0, 2, 3))
            return dw, db
        return dw
    return _wgrad_windowed(xp, dy, hf, wf, stride, hob, wob, machine,
                           interpret, out_dtype, groups, dilation,
                           z, activation, with_db)


def _wgrad_windowed(xp: jnp.ndarray, dy: jnp.ndarray, hf: int, wf: int,
                    stride: int, hob: Optional[int], wob: Optional[int],
                    machine: MachineModel, interpret: bool,
                    out_dtype, groups: int = 1,
                    dilation=(1, 1), z=None, activation=None,
                    with_db: bool = False):
    n, ciblk, hi, wi, cib = xp.shape
    n2, coblk, ho, wo, cob = dy.shape
    assert n == n2, (xp.shape, dy.shape)
    assert ciblk % groups == 0 and coblk % groups == 0, \
        (xp.shape, dy.shape, groups)
    cigblk = ciblk // groups
    cogblk = coblk // groups

    blk = choose_wgrad_blocking(ho, wo, hf, wf, stride, machine=machine,
                                cob=cob, cib=cib, hob=hob, wob=wob,
                                in_dtype_bytes=xp.dtype.itemsize,
                                dilation=dilation,
                                fused_prologue=z is not None,
                                fused_bias=with_db)
    hob, wob = blk.hob, blk.wob
    hib, wib = halo_dims(hob, wob, hf, wf, stride, dilation)

    operands = [xp, dy]
    in_specs = [
        halo_window_spec(hib, wib, cib,
                         *window_steps(ho, wo, hob, wob, stride),
                         lambda co, ci, b, th, tw:
                         (b, (co // cogblk) * cigblk + ci, th, tw)),
        tile_spec(hob, wob, cob,
                  lambda co, ci, b, th, tw: (b, co, th, tw)),
    ]
    if z is not None:
        operands.append(z)
        in_specs.append(tile_spec(hob, wob, cob,
                                  lambda co, ci, b, th, tw: (b, co, th, tw)))

    out_specs = weight_spec(hf, wf, cib, cob,
                            lambda co, ci, b, th, tw: (co, ci))
    out_shape = jax.ShapeDtypeStruct((coblk, cigblk, hf, wf, cib, cob),
                                     out_dtype or xp.dtype)
    scratch = [pltpu.VMEM((hf, wf, cib, cob), jnp.float32)]
    if with_db:
        out_specs = [out_specs,
                     bias_spec(cob, lambda co, ci, b, th, tw: (co,))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((coblk, 1, cob), jnp.float32)]
        scratch = [scratch[0], pltpu.VMEM((1, cob), jnp.float32)]

    # the weight-gradient block walk is per group: only the cigblk input
    # blocks of output block co's own group are contracted (the other
    # cross-group products are structural zeros of the block-diagonal weight
    # and are simply never computed)
    grid = (coblk, cigblk, n, ho // hob, wo // wob)
    return unpencil(pl.pallas_call(
        partial(_wgrad_kernel, hf=hf, wf=wf, hob=hob, wob=wob,
                stride=stride, has_z=z is not None, activation=activation,
                with_db=with_db, dilation=dilation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(machine, wgrad_semantics(with_db)),
        interpret=interpret,
    )(*operands), with_db)


# ---------------------------------------------------------------------------
# custom VJP: jax.grad flows through the kernel family
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _conv(x, w, bias, residual, spec, activation, hob, wob, machine,
          interpret, precision, stream, hso, gap):
    """Primal: the fully fused forward kernel (inference takes this path —
    bias + activation + residual skip-add inside the epilogue, the GAP
    partial-sum riding the flush; output written once).  The geometry —
    stride, normalized pads, groups, dilation — rides as one frozen
    ``ConvSpec`` (hashable, so it is a valid nondiff/static arg).  Operands
    are cast to the policy dtype here — the one down-cast of the forward;
    bias stays in its master dtype (the epilogue adds it on the f32
    accumulator anyway).  With ``gap`` the return is the pooled ``[N, Co]``
    features — the map is written but never re-read."""
    op = precision.op_dtype
    xp = pad_blocked(x.astype(op), *spec.pads)
    r = None if residual is None else residual.astype(op)
    out = _forward_impl(xp, w.astype(op), bias, spec.stride, activation,
                        hob, wob, machine, interpret, stream, hso,
                        spec.groups, spec.dilation, residual=r, gap=gap)
    if gap:
        _, pooled = out
        n, coblk, cob = pooled.shape
        return pooled.reshape(n, coblk * cob)
    return out


def _conv_fwd(x, w, bias, residual, spec, activation, hob, wob, machine,
              interpret, precision, stream, hso, gap):
    """VJP forward: the same kernel computes the *pre-activation* tile z (the
    epilogue residual the backward needs — relu/gelu cotangents are functions
    of z, not of the activated output); the activation, skip-add and pool are
    applied outside, each in f32 with one down-cast — training pays one extra
    pass the inference primal fuses away, because z must exist in HBM as a
    backward residual either way.  For linear epilogues z IS the
    pre-residual output and no extra residual is kept.

    Residuals are stored at the policy dtypes (operand-cast xp/w, z at
    ``policy.residual`` — the halved training working set); zero-size dtype
    tokens remember the primal x/w/residual dtypes so the backward can
    up-cast its cotangents exactly once, at the very end.
    """
    op = precision.op_dtype
    xp = pad_blocked(x.astype(op), *spec.pads)
    wq = w.astype(op)
    z = _forward_impl(xp, wq, bias, spec.stride, None, hob, wob, machine,
                      interpret, stream, hso, spec.groups, spec.dilation)
    linear = activation in (None, "linear")
    out = z if linear else apply_activation(
        z.astype(jnp.float32), activation).astype(z.dtype)
    if residual is not None:
        out = (out.astype(jnp.float32)
               + residual.astype(jnp.float32)).astype(z.dtype)
    if gap:
        n, coblk, _, _, cob = out.shape
        out = jnp.mean(out.astype(jnp.float32),
                       axis=(2, 3)).reshape(n, coblk * cob).astype(z.dtype)
    res = (xp, wq, bias,
           None if linear else z.astype(precision.residual_dtype),
           None if residual is None else jnp.zeros((0,), residual.dtype),
           jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))
    return out, res


def _conv_bwd(spec, activation, hob, wob, machine, interpret,
              precision, stream, hso, gap, res, g):
    """The backward kernels inherit the ``stream`` routing (an explicit
    override forces all three kernels onto one path; None lets each kernel
    fall back only where its own window inequality misfits).  Strip heights
    are per-kernel model choices — the forward's ``hso`` is not theirs.

    The activation cotangent is *not* materialized here: the raw map
    cotangent ``g`` and the saved pre-activation ``z`` go to both backward
    kernels, which form ``dz = g * act'(z)`` on tile load
    (``cotangent_prologue``) and — when a bias exists — accumulate
    ``db = Σ dz`` in the wgrad kernel's flush-once scratch.  Only a
    stream-routed direction falls back to the XLA pointwise op."""
    xp, wq, bias, z, r_token, x_token, w_token = res
    hf, wf = wq.shape[2], wq.shape[3]
    stride, pads = spec.stride, spec.pads
    groups, dilation = spec.groups, spec.dilation
    op = precision.op_dtype

    if gap:
        # un-pool: the mean's cotangent is the pooled cotangent spread
        # uniformly over the map (computed in f32, one down-cast)
        n = xp.shape[0]
        coblk, cob = wq.shape[0], wq.shape[5]
        hi_p, wi_p = xp.shape[2], xp.shape[3]
        dil_h, dil_w = dilation
        ho = (hi_p - ((hf - 1) * dil_h + 1)) // stride + 1
        wo = (wi_p - ((wf - 1) * dil_w + 1)) // stride + 1
        gm = g.reshape(n, coblk, 1, 1, cob).astype(jnp.float32) / (ho * wo)
        g = jnp.broadcast_to(gm, (n, coblk, ho, wo, cob))
    g = g.astype(op)                         # the backward kernels' operand

    # residual cotangent: the skip branch is additive after the activation,
    # so its cotangent is the map cotangent itself (up-cast once)
    dres = None if r_token is None else g.astype(r_token.dtype)

    # input gradient w.r.t. the padded input, then strip the pads (rows the
    # forward never touched — beyond the dgrad extents — stay zero); the
    # activation prologue rides inside the kernel
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    hi_p, wi_p = xp.shape[2], xp.shape[3]
    hi, wi = hi_p - ph_lo - ph_hi, wi_p - pw_lo - pw_hi
    dxp = direct_conv2d_dgrad_pallas(g, wq, stride=stride, machine=machine,
                                     interpret=interpret, stream=stream,
                                     groups=groups, dilation=dilation,
                                     z=z, activation=activation)
    eh, ew = dxp.shape[2], dxp.shape[3]
    dxp = jnp.pad(dxp, ((0, 0), (0, 0), (0, hi_p - eh), (0, wi_p - ew),
                        (0, 0)))
    # the single cotangent up-cast
    dx = dxp[:, :, ph_lo:ph_lo + hi, pw_lo:pw_lo + wi, :].astype(x_token.dtype)

    # dw leaves the wgrad kernel in f32 and reaches the (f32 master) weight
    # dtype directly — never round-tripped through the operand dtype; db
    # (the epilogue broadcast transposed — pencil sums in f32, cast to the
    # master bias dtype once) flushes from the same kernel's scratch
    if bias is not None:
        dw, db32 = direct_conv2d_wgrad_pallas(
            xp, g, hf, wf, stride=stride, machine=machine,
            interpret=interpret, out_dtype=jnp.float32, stream=stream,
            groups=groups, dilation=dilation, z=z, activation=activation,
            with_db=True)
        db = db32.astype(bias.dtype)
    else:
        dw = direct_conv2d_wgrad_pallas(
            xp, g, hf, wf, stride=stride, machine=machine,
            interpret=interpret, out_dtype=jnp.float32, stream=stream,
            groups=groups, dilation=dilation, z=z, activation=activation)
        db = None
    dw = dw.astype(w_token.dtype)
    return dx, dw, db, dres


_conv.defvjp(_conv_fwd, _conv_bwd)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

@partial(jax.jit,
         static_argnames=("stride", "padding", "activation", "hob", "wob",
                          "machine", "interpret", "precision", "stream",
                          "hso", "groups", "dilation", "gap"))
def direct_conv2d_blocked_pallas(x: jnp.ndarray, w: jnp.ndarray,
                                 bias: Optional[jnp.ndarray] = None,
                                 stride: int = 1,
                                 padding: Padding = "VALID",
                                 activation: Optional[str] = None,
                                 hob: Optional[int] = None,
                                 wob: Optional[int] = None,
                                 machine: Optional[MachineModel] = None,
                                 interpret: Optional[bool] = None,
                                 precision: Precision | str = F32,
                                 stream: Optional[bool] = None,
                                 hso: Optional[int] = None,
                                 groups: int = 1,
                                 dilation: int | tuple = 1,
                                 residual: Optional[jnp.ndarray] = None,
                                 gap: bool = False,
                                 ) -> jnp.ndarray:
    """Tiled + fused direct convolution on the paper's blocked layouts,
    differentiable end to end (custom VJP -> the dgrad/wgrad kernels).

    x: [N, Ci/Cib, Hi, Wi, Cib]; w: [Co/Cob, Ci/Cib, Hf, Wf, Cib, Cob];
    bias: [Co/Cob, Cob] or None -> [N, Co/Cob, Ho, Wo, Cob] in the policy's
    operand dtype (layers chain in bf16 under the bf16 policy).

    ``padding`` is stride-aware (TF SAME semantics); ``hob``/``wob`` (output
    rows/cols per spatial tile) default to the analytical blocking model's
    choice for ``machine`` and must divide Ho/Wo.  ``jax.grad`` through this
    function runs the transposed-window dgrad and per-tile wgrad Pallas
    kernels (their tiles sized by ``choose_dgrad_blocking`` /
    ``choose_wgrad_blocking`` for the same ``machine``), with bias and
    activation cotangents taken from the fused epilogue's residuals.

    ``precision`` is the mixed-precision policy (a ``Precision`` or
    "f32"/"bf16"): operand casts on entry, f32 accumulators throughout,
    residuals at the policy dtype, one cotangent up-cast on exit —
    see the module docstring and DESIGN.md §10.

    ``stream`` selects the kernel variant (DESIGN.md §11–§12): None
    (default) probes the window VMEM inequality pre-launch and serves the
    streamed halo-DMA variant when it misfits even at ``hob = wob = 1``
    (what used to be a hard raise); True forces the streamed path (``hso``
    optionally pins its strip height); False pins the window path, letting
    the misfit propagate; a ``core.dispatch.KernelRoute`` resolves each
    direction independently (what ``ConvDispatcher`` passes when it routes
    a layer).  The knob rides the custom VJP too, so dgrad/wgrad route
    consistently.

    ``groups``/``dilation`` (DESIGN.md §13): weights are grouped-HWIO
    blocked — ``[Co/Cob, Cig/Cib, Hf, Wf, Cib, Cob]`` with ``Cig = Ci //
    groups`` — and the grid walks a block-diagonal reduction (each output
    block contracts only its own group's input blocks); dilation strides
    the filter taps and widens the halo, with SAME padding resolved against
    the effective extent.  Both ride the custom VJP (block-diagonal dgrad/
    wgrad).  The streamed variant stays dense — grouped/dilated launches
    pin the window path.

    ``residual``/``gap`` are the fused epilogue riders (DESIGN.md §14):
    ``residual`` is an output-shaped blocked map skip-added *after* the
    activation on the f32 accumulator (``out = act(z + bias) + r``, one
    down-cast); ``gap=True`` accumulates each flushed tile into a fused
    global-average-pool and returns the pooled ``[N, Co]`` features
    instead of the map.  Both are differentiable — the residual's
    cotangent is the map cotangent itself, and the backward kernels fuse
    ``dz = g * act'(z)`` (plus ``db``) in-kernel.
    """
    machine = resolve_machine(machine)
    interpret = resolve_interpret(interpret)
    n, ciblk_x, hi, wi, cib_x = x.shape
    coblk, _, hf, wf, _, cob = w.shape
    pi, po = (lane_pad(machine, interpret, cib_x, cob) if groups == 1
              else (0, 0))
    spec = ConvSpec.make(n, hi, wi, ciblk_x * (cib_x + pi),
                         coblk * (cob + po), hf, wf,
                         stride=stride, padding=padding, groups=groups,
                         dilation=dilation)
    out = _conv(pad_lanes(x, pi), pad_lanes(pad_lanes(w, po), pi, axis=4),
                pad_lanes(bias, po), pad_lanes(residual, po), spec,
                activation, hob, wob, machine, interpret,
                resolve_precision(precision), stream, hso, gap)
    return crop_lanes(out, cob, gap, coblk)
