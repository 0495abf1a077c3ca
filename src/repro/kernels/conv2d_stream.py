"""Streamed (halo-DMA) direct-convolution kernels — DESIGN.md §11.

The window-path kernels (``kernels/direct_conv2d.py``) let BlockSpec windows
pull the full halo'd ``[Hib, Wib, Cib]`` patch per grid step, which Pallas
double-buffers — fatal for shapes whose 2-D VMEM inequality misfits even at
``Hob = Wob = 1`` (pathologically deep pinned pencils against small budgets:
the ``2x`` on the ``Hf*Wf*Cib*Cob`` weight tile dominates).  This module is
the drop-in the shared grid machinery (``kernels/conv2d_common.py``) was
built for: the big operands stay in HBM (``memory_space=ANY``) and the
kernel drives its own DMA —

  * the weight tile is copied **once** per grid step into singly-resident
    scratch (no Pallas double-buffering: the 2x disappears);
  * the input band streams through a **2-slot ring of row-strips** with a
    manually double-buffered ``pltpu.make_async_copy`` pipeline: strip
    ``k+1``'s copy is in flight while strip ``k`` is contracted, with
    ``wait`` guards at the seams;
  * the ``Hf - stride`` row overlap between adjacent strips is **fetched
    from HBM exactly once**: each new strip's leading halo rows are copied
    VMEM→VMEM from the previous slot's tail before its fresh rows land.

The resident set is therefore ~2 strips + one weight tile + the accumulator
(``core.blocking.stream_resident_bytes`` is the single source), opening the
regime the window inequality cannot satisfy and killing the per-strip halo
re-fetch tax (``memory_model.bytes_halo_refetch``).

Three variants share the structure:

  forward  grid ``(N, Co/Cob, Ho/Hob, Wo/Wob, Ci/Cib)`` — the window grid,
           but each step streams its band as ``Hob/Hso`` strips;
  dgrad    the same kernel body over the dilated, ``Hf-1``-halo-padded
           cotangent (taps mirrored, pencil contraction flipped, stride 1 —
           ``transpose=True``);
  wgrad    grid ``(Co/Cob, Ci/Cib, N, Wo/Wob)`` with *both* operands
           streamed (halo'd x ring + disjoint cotangent ring) and the
           ``[Hf, Wf, Cib, Cob]`` f32 accumulator flushed to HBM by manual
           DMA — the window path's double-buffered VMEM output block does
           not exist here, which is what lets wgrad fit wherever the
           streamed forward does.

These are implementation entry points on *already-padded* blocked operands;
the routed public API (``stream=`` knob, auto-fallback on
``VmemMisfitError``) lives on ``direct_conv2d_blocked_pallas`` and the
backward wrappers in ``kernels/direct_conv2d.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import (MachineModel, choose_stream_blocking,
                                 choose_stream_dgrad_blocking,
                                 choose_stream_wgrad_blocking, dgrad_extents)
from repro.core.direct_conv import pad_blocked
from repro.utils.faults import inject as _inject_fault
from .conv2d_common import (bias_spec, compiler_params, epilogue_flush,
                            first_step, forward_semantics, gap_spec,
                            gap_update, last_step,
                            pencils, strided_source, tap_windows, tile_spec,
                            unpencil)

__all__ = ["stream_forward", "stream_dgrad", "stream_wgrad"]


def _strip_geometry(hso: int, wob: int, hf: int, wf: int, stride: int):
    """(ring-slot rows, ring-slot cols, reusable halo rows) for one strip."""
    hin = (hso - 1) * stride + hf
    wib = (wob - 1) * stride + wf
    halo = max(hf - stride, 0)
    return hin, wib, halo


# ---------------------------------------------------------------------------
# shared streamed body: forward (transpose=False) and dgrad (transpose=True)
# ---------------------------------------------------------------------------

def _stream_conv_kernel(x_any, w_any, *rest, hf, wf, hob, wob, hso, stride,
                        activation, has_bias, has_residual, has_gap, hw,
                        transpose):
    """One grid step: DMA the weight tile once, stream the input band as
    ``hob/hso`` ring strips (copy strip k+1 while contracting strip k), and
    accumulate into the persistent f32 scratch; flush on the last reduction
    step.  ``transpose`` flips the kernel into its dgrad form: weight block
    indexed ``(red, cout)`` instead of ``(cout, red)``, taps mirrored, the
    matmul contracting lanes instead of the pencil depth.

    The fused epilogue riders (residual tile, GAP partial-sum) are
    forward-only: they ride the *Pallas* pipeline next to the bias pencil
    and output block — only touched at the flush, so they never interact
    with the manual strip ring."""
    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    r_ref = rest.pop(0) if has_residual else None
    o_ref = rest.pop(0)
    g_ref = rest.pop(0) if has_gap else None
    wgt, ring, acc_ref = rest[0], rest[1], rest[2]
    gacc_ref = rest[3] if has_gap else None
    sem = rest[-1]

    b = pl.program_id(0)
    cout = pl.program_id(1)      # output channel-block axis (Ci for dgrad)
    th = pl.program_id(2)
    tw = pl.program_id(3)
    red = pl.program_id(4)       # reduction channel-block axis (the revisit)

    hin, wib, halo = _strip_geometry(hso, wob, hf, wf, stride)
    nstrips = hob // hso
    row0 = th * hob * stride
    # a launch with one column tile copies whole rows: a ``pl.ds`` of the
    # full width is still a slice Mosaic must prove tile-aligned, which it
    # cannot for packed bf16 rows of unaligned width
    cols = (pl.ds(tw * wob * stride, wib) if wib < x_any.shape[3]
            else slice(None))

    # weights: one DMA into singly-resident scratch — the streamed variant's
    # headline saving (the window path pays 2x for Pallas pipelining)
    wi, wj = (red, cout) if transpose else (cout, red)
    wcp = pltpu.make_async_copy(w_any.at[wi, wj], wgt, sem.at[2])
    wcp.start()

    def strip_dma(k: int):
        # strip 0 fetches its whole halo'd extent; every later strip skips
        # the leading ``halo`` rows — those arrive VMEM->VMEM from the
        # previous slot's tail (the seam copy below), never from HBM again
        lo = 0 if k == 0 else halo
        return pltpu.make_async_copy(
            x_any.at[b, red, pl.ds(row0 + k * hso * stride + lo, hin - lo),
                     cols, :],
            ring.at[k % 2, pl.ds(lo, hin - lo)], sem.at[k % 2])

    @pl.when(first_step((4,)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    strip_dma(0).start()
    wcp.wait()
    for k in range(nstrips):                  # static unroll: hob/hso strips
        strip_dma(k).wait()
        if k + 1 < nstrips:
            # seam discipline: the halo rows move between ring slots before
            # the next fresh-row DMA launches (disjoint row ranges, and the
            # previous slot's compute finished last iteration — vector ops
            # are synchronous, only the DMAs are async)
            if halo:
                ring[(k + 1) % 2, 0:halo] = ring[k % 2, hin - halo:hin]
            strip_dma(k + 1).start()          # in flight while k contracts
        rows = pl.ds(k * hso * wob, hso * wob)

        def taps(src, dtype, lead, rows=rows):
            for (dh, dw), win in tap_windows(src, hf, wf, hso, wob, stride,
                                             dtype=dtype, lead=lead):
                if transpose:
                    # [Hso*Wob, Cob] x [Cib, Cob] -> [Hso*Wob, Cib]
                    acc_ref[rows] += jax.lax.dot_general(
                        win, wgt[hf - 1 - dh, wf - 1 - dw],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                else:
                    acc_ref[rows] += jnp.dot(
                        win, wgt[dh, dw], preferred_element_type=jnp.float32)

        strided_source(ring, stride, taps, (k % 2,))

    gap_first = first_step((2, 3)) if has_gap else None
    gap_last = last_step((2, 3)) if has_gap else None

    @pl.when(last_step((4,)))
    def _flush():
        tile = epilogue_flush(o_ref, acc_ref[...], hob, wob, b_ref,
                              activation, r_ref)
        if has_gap:
            gap_update(g_ref, gacc_ref, tile, hw, gap_first, gap_last)


def _any_spec() -> pl.BlockSpec:
    """A whole-array operand left in HBM for the kernel's manual DMA."""
    return pl.BlockSpec(memory_space=pltpu.HBM)


def stream_forward(xp: jnp.ndarray, w: jnp.ndarray, bias, stride: int,
                   activation, hob, wob, hso,
                   machine: MachineModel, interpret: bool,
                   residual=None, gap: bool = False):
    """Streamed forward on an already-padded blocked input (always VALID).

    Same contract as the window path's ``_forward_impl`` — identical grid,
    epilogue and output layout, so the two are interchangeable (and
    bit-identical: per output element the (Ci-block, tap) contraction order
    is the same; strips only partition rows, which are independent
    accumulators).  Tiles come from ``choose_stream_blocking`` with the
    pencils pinned to the operand layouts.

    ``residual``/``gap`` ride the Pallas pipeline (the residual tile as a
    Blocked operand next to the bias pencil, the pooled pencil + f32
    scratch next to the output block) — both are flush-time only, so the
    manual DMA ring is untouched.  With ``gap`` the return is the
    ``(map, pooled)`` pair, matching ``_forward_windowed``.
    """
    _inject_fault("kernel.launch")      # fires at trace time (jit caller)
    n, ciblk, hi, wi_, cib = xp.shape
    coblk, ciblk2, hf, wf, cib2, cob = w.shape
    assert (ciblk, cib) == (ciblk2, cib2), (xp.shape, w.shape)
    ho = (hi - hf) // stride + 1
    wo = (wi_ - wf) // stride + 1

    blk = choose_stream_blocking(hi, wi_, ciblk * cib, coblk * cob, hf, wf,
                                 stride, machine=machine, cob=cob, cib=cib,
                                 hob=hob, wob=wob, hso=hso,
                                 in_dtype_bytes=xp.dtype.itemsize,
                                 fused_residual=residual is not None,
                                 fused_gap=gap)
    hob, wob, hso = blk.hob, blk.wob, blk.hso
    hin, wib, _ = _strip_geometry(hso, wob, hf, wf, stride)

    has_bias = bias is not None
    has_residual = residual is not None
    operands = [xp, w]
    in_specs = [_any_spec(), _any_spec()]
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
    scratch = [pltpu.VMEM((hf, wf, cib, cob), xp.dtype),
               pltpu.VMEM((2, hin, wib, cib), xp.dtype),
               pltpu.VMEM((hob * wob, cob), jnp.float32)]
    if gap:
        out_specs = [out_specs,
                     gap_spec(cob, lambda b, co, th, tw, ci: (b, co))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n, coblk, 1, cob), xp.dtype)]
        scratch.append(pltpu.VMEM((1, cob), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((3,)))

    grid = (n, coblk, ho // hob, wo // wob, ciblk)
    return unpencil(pl.pallas_call(
        partial(_stream_conv_kernel, hf=hf, wf=wf, hob=hob, wob=wob, hso=hso,
                stride=stride, activation=activation, has_bias=has_bias,
                has_residual=has_residual, has_gap=gap, hw=ho * wo,
                transpose=False),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(machine, forward_semantics(gap)),
        interpret=interpret,
    )(*operands), gap)


def stream_dgrad(dy: jnp.ndarray, w: jnp.ndarray, stride: int,
                 hob, wob, hso, machine: MachineModel,
                 interpret: bool) -> jnp.ndarray:
    """Streamed input gradient: the forward body with ``transpose=True`` over
    the stride-dilated, ``Hf-1``-halo-padded cotangent (windows slide by 1 —
    the stride lives in the dilation, exactly the window dgrad's contract).
    Returns the gradient w.r.t. the padded input at the touched extents
    ``E = (out-1)*stride + filter``; the custom VJP pads/crops.
    """
    _inject_fault("kernel.launch")
    n, coblk, ho, wo, cob = dy.shape
    coblk2, ciblk, hf, wf, cib, cob2 = w.shape
    assert (coblk, cob) == (coblk2, cob2), (dy.shape, w.shape)

    if stride > 1:
        dyd = jnp.zeros((n, coblk, (ho - 1) * stride + 1,
                         (wo - 1) * stride + 1, cob), dy.dtype)
        dyd = dyd.at[:, :, ::stride, ::stride, :].set(dy)
    else:
        dyd = dy
    dyp = pad_blocked(dyd, (hf - 1, hf - 1), (wf - 1, wf - 1))

    eh, ew = dgrad_extents(ho, wo, hf, wf, stride)
    blk = choose_stream_dgrad_blocking(ho, wo, ciblk * cib, coblk * cob,
                                       hf, wf, stride, machine=machine,
                                       cib=cib, cob=cob, hob=hob, wob=wob,
                                       hso=hso,
                                       in_dtype_bytes=dy.dtype.itemsize)
    hob, wob, hso = blk.hob, blk.wob, blk.hso
    hin, wib, _ = _strip_geometry(hso, wob, hf, wf, 1)

    grid = (n, ciblk, eh // hob, ew // wob, coblk)
    return pl.pallas_call(
        partial(_stream_conv_kernel, hf=hf, wf=wf, hob=hob, wob=wob, hso=hso,
                stride=1, activation=None, has_bias=False,
                has_residual=False, has_gap=False, hw=eh * ew,
                transpose=True),
        grid=grid,
        in_specs=[_any_spec(), _any_spec()],
        out_specs=tile_spec(hob, wob, cib,
                            lambda b, ci, th, tw, co: (b, ci, th, tw)),
        out_shape=jax.ShapeDtypeStruct((n, ciblk, eh, ew, cib), dy.dtype),
        scratch_shapes=[pltpu.VMEM((hf, wf, cib, cob), dy.dtype),
                        pltpu.VMEM((2, hin, wib, cob), dy.dtype),
                        pltpu.VMEM((hob * wob, cib), jnp.float32),
                        pltpu.SemaphoreType.DMA((3,))],
        compiler_params=compiler_params(
            machine, ("parallel",) * 4 + ("arbitrary",)),
        interpret=interpret,
    )(dyp, w)


# ---------------------------------------------------------------------------
# streamed wgrad: both operands ringed, accumulator flushed by manual DMA
# ---------------------------------------------------------------------------

def _stream_wgrad_kernel(x_any, dy_any, o_any, xring, dyring, acc_ref, sem,
                         osem, *, hf, wf, ho, wob, hso, stride):
    """One (Co, Ci, n, tw) step: stream the full row extent as ``Ho/Hso``
    strip pairs (halo'd x strip + matching disjoint cotangent strip, each on
    its own double-buffered ring/semaphore lane) and reduce every tap's
    ``[Hso*Wob]``-position contraction into the resident weight-gradient
    accumulator.  The accumulator is the only weight-sized buffer: on the
    last reduction step it DMAs straight to the HBM output — there is no
    VMEM output block at all."""
    co, ci, b, tw = (pl.program_id(i) for i in range(4))
    hin, wib, halo = _strip_geometry(hso, wob, hf, wf, stride)
    nstrips = ho // hso
    # whole rows when there is one column tile (see _stream_conv_kernel)
    xcols = (pl.ds(tw * wob * stride, wib) if wib < x_any.shape[3]
             else slice(None))
    dycols = pl.ds(tw * wob, wob) if wob < dy_any.shape[3] else slice(None)

    def x_dma(k: int):
        lo = 0 if k == 0 else halo
        return pltpu.make_async_copy(
            x_any.at[b, ci, pl.ds(k * hso * stride + lo, hin - lo),
                     xcols, :],
            xring.at[k % 2, pl.ds(lo, hin - lo)], sem.at[0, k % 2])

    def dy_dma(k: int):
        return pltpu.make_async_copy(
            dy_any.at[b, co, pl.ds(k * hso, hso), dycols, :],
            dyring.at[k % 2], sem.at[1, k % 2])

    @pl.when(first_step((2, 3)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_dma(0).start()
    dy_dma(0).start()
    for k in range(nstrips):
        x_dma(k).wait()
        dy_dma(k).wait()
        if k + 1 < nstrips:
            if halo:
                xring[(k + 1) % 2, 0:halo] = xring[k % 2, hin - halo:hin]
            x_dma(k + 1).start()
            dy_dma(k + 1).start()
        dyf = dyring[k % 2].reshape(hso * wob, dyring.shape[-1])

        def taps(src, dtype, lead, dyf=dyf):
            for (dh, dw), win in tap_windows(src, hf, wf, hso, wob, stride,
                                             dtype=dtype, lead=lead):
                # [Hso*Wob, Cib] x [Hso*Wob, Cob] -> [Cib, Cob]
                acc_ref[dh, dw] = acc_ref[dh, dw] + jax.lax.dot_general(
                    win, dyf, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        strided_source(xring, stride, taps, (k % 2,))

    @pl.when(last_step((2, 3)))
    def _flush():
        out = pltpu.make_async_copy(acc_ref, o_any.at[co, ci], osem)
        out.start()
        out.wait()


def stream_wgrad(xp: jnp.ndarray, dy: jnp.ndarray, hf: int, wf: int,
                 stride: int, wob, hso, machine: MachineModel,
                 interpret: bool, out_dtype=None) -> jnp.ndarray:
    """Streamed weight gradient on the forward's padded input + cotangent.

    The kernel always emits f32 (the accumulator DMAs out untouched — under
    the mixed-precision policy ``dw`` reaches the f32 masters with no bf16
    round-trip anyway); the requested ``out_dtype`` is applied outside the
    kernel, costing zero VMEM.
    """
    _inject_fault("kernel.launch")
    n, ciblk, hi, wi_, cib = xp.shape
    n2, coblk, ho, wo, cob = dy.shape
    assert n == n2, (xp.shape, dy.shape)

    blk = choose_stream_wgrad_blocking(ho, wo, hf, wf, stride,
                                       machine=machine, cob=cob, cib=cib,
                                       wob=wob, hso=hso,
                                       in_dtype_bytes=xp.dtype.itemsize)
    wob, hso = blk.wob, blk.hso
    hin, wib, _ = _strip_geometry(hso, wob, hf, wf, stride)

    grid = (coblk, ciblk, n, wo // wob)
    out = pl.pallas_call(
        partial(_stream_wgrad_kernel, hf=hf, wf=wf, ho=ho, wob=wob, hso=hso,
                stride=stride),
        grid=grid,
        in_specs=[_any_spec(), _any_spec()],
        out_specs=_any_spec(),
        out_shape=jax.ShapeDtypeStruct((coblk, ciblk, hf, wf, cib, cob),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, hin, wib, cib), xp.dtype),
                        pltpu.VMEM((2, hso, wob, cob), dy.dtype),
                        pltpu.VMEM((hf, wf, cib, cob), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=compiler_params(
            machine, ("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xp, dy)
    return out.astype(out_dtype or xp.dtype)
