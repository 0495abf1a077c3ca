"""Shared grid / BlockSpec / epilogue machinery for the blocked direct-conv
kernel family (forward, dgrad, wgrad — DESIGN.md §2, §7, §9).

All three kernels walk the same kind of grid — a batch-like axis, a channel
-block output axis, two spatial tile axes and one (or three) reduction axes —
over operands in the paper's blocked layouts.  What they share lives here so
that a kernel is only its contraction body:

* ``halo_dims`` / ``halo_window_spec`` — the overlapping (halo'd) input
  window that plain Blocked indexing cannot express.  Adjacent tiles overlap
  by the ``Hf - stride`` / ``Wf - stride`` halos, so the BlockSpec indexes
  by element (every block dim a ``pl.Element``): the index map returns
  ``tile * tile_extent * stride`` directly.  Safe with no out-of-bounds
  semantics because every tile extent divides the corresponding output
  extent (``core.blocking`` snaps to divisors).
* ``weight_spec`` / ``tile_spec`` / ``bias_spec`` — the non-overlapping
  operand blocks, parameterized by how the kernel's grid axes map onto the
  operand's leading (batch, channel-block) dims.
* ``tap_windows`` — the in-VMEM strided views, one per filter tap: the rows
  of the im2col matrix that is never materialized (not in HBM, not in VMEM).
* ``first_step`` / ``last_step`` — reduction-axis guards for the
  init-accumulator / flush-epilogue pattern (the output block's index map is
  constant along reduction axes, so Pallas revisits the same block).
* ``epilogue_flush`` — the single down-cast store with the fused
  bias + activation (+ residual skip-add) applied on the f32 accumulator
  (forward); dgrad reuses it with no bias/activation.  It returns the
  stored tile so callers can chain further fused consumers.
* ``gap_update`` / ``gap_spec`` — the global-average-pool rider: each
  flushed tile's spatial sum lands in a persistent f32 scratch pencil and
  the pooled ``[1, Cb]`` output is written once after the last spatial
  tile (DESIGN.md §14 — partial sums stay f32 for the same reason the
  matmul accumulator does).
* ``cotangent_prologue`` — the backward twin of the fused epilogue: the
  dgrad/wgrad kernels take the *raw* incoming cotangent ``g`` plus the
  saved pre-activation ``z`` and compute ``dz = g * act'(z)`` on tile
  load, in f32, with the same cast discipline the unfused XLA pointwise
  op used — so the fused backward is bit-identical while never
  materializing ``dz`` in HBM.

Every kernel is parameterized by the same ``core.blocking`` output
(``Blocking`` for forward/dgrad, ``choose_wgrad_blocking`` for wgrad), which
is the point of the refactor: the streamed halo-DMA variant
(``kernels/conv2d_stream.py``, DESIGN.md §11) reuses ``tap_windows``, the
reduction guards, ``epilogue_flush`` and the non-overlapping operand specs
verbatim — only the halo'd window spec is replaced by its manual
``make_async_copy`` ring.
"""
from __future__ import annotations

from typing import Callable, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.direct_conv import apply_activation

__all__ = [
    "halo_dims", "halo_window_spec", "weight_spec", "tile_spec", "bias_spec",
    "lane_pad", "pad_lanes", "crop_lanes", "pencils", "unpencil",
    "gap_spec", "window_steps", "tap_windows", "strided_source",
    "compiler_params", "forward_semantics", "wgrad_semantics",
    "first_step", "last_step", "epilogue_flush", "gap_update", "tree_sum",
    "cotangent_prologue",
]

# A map from the kernel's grid indices to the operand's leading block
# indices.  Forward walks (n, co, th, tw, ci), dgrad (n, ci, th, tw, co),
# wgrad (co, ci, n, th, tw) — the specs below are grid-order agnostic; each
# kernel passes the pick function that reorders its grid ids.
GridPick = Callable[..., Tuple]


def halo_dims(hob: int, wob: int, hf: int, wf: int, stride: int = 1,
              dilation: Tuple[int, int] = (1, 1)) -> Tuple[int, int]:
    """Input rows/cols feeding one (hob x wob) output tile, halo included.

    Dilation widens the halo to the *effective* filter extent
    ``(hf-1)*dh + 1`` — the taps are spread out, the window must cover the
    outermost one."""
    dh, dw = dilation
    return ((hob - 1) * stride + (hf - 1) * dh + 1,
            (wob - 1) * stride + (wf - 1) * dw + 1)


def halo_window_spec(hib: int, wib: int, cb: int, hstep: int, wstep: int,
                     pick: GridPick) -> pl.BlockSpec:
    """Overlapping input window over a blocked map ``[B, C/Cb, H, W, Cb]``.

    ``hstep``/``wstep`` are the *element* offsets between adjacent tiles'
    windows (``hob * stride`` / ``wob * stride``; 0 where the axis has one
    tile — see ``window_steps``); ``pick`` maps the grid ids
    to ``(batch, channel_block, tile_h, tile_w)``.  Element-offset indexing
    because adjacent windows overlap by the filter halo — Blocked indexing
    only expresses multiples of the block shape.  Every dim is a
    ``pl.Element`` (Mosaic refuses a spec that mixes Element and Blocked
    dims), so the index map returns element offsets on all five axes; the
    unit batch and channel-block dims make those offsets the block ids.
    """
    def index_map(*ids):
        b, c, th, tw = pick(*ids)
        # one tile along an axis: a literal 0, which Mosaic can prove
        # sublane-aligned (``tw * wstep`` it cannot, whatever wstep is)
        return (b, c, th * hstep if hstep else 0, tw * wstep if wstep else 0,
                0)

    return pl.BlockSpec(tuple(pl.Element(d) for d in (1, 1, hib, wib, cb)),
                        index_map)


def window_steps(ho: int, wo: int, hob: int, wob: int,
                 stride: int) -> Tuple[int, int]:
    """``(hstep, wstep)`` for :func:`halo_window_spec`: the element offsets
    between adjacent tiles, 0 for an axis the launch does not tile."""
    return (hob * stride if ho > hob else 0, wob * stride if wo > wob else 0)


def weight_spec(hf: int, wf: int, cib: int, cob: int,
                pick: GridPick) -> pl.BlockSpec:
    """One ``[Hf, Wf, Cib, Cob]`` tile of the paper's kernel layout
    ``[Co/Cob, Ci/Cib, Hf, Wf, Cib, Cob]``; ``pick`` -> (co_block, ci_block).
    """
    def index_map(*ids):
        co, ci = pick(*ids)
        return (co, ci, 0, 0, 0, 0)

    return pl.BlockSpec((1, 1, hf, wf, cib, cob), index_map)


def tile_spec(hob: int, wob: int, cb: int, pick: GridPick) -> pl.BlockSpec:
    """A non-overlapping ``[hob, wob, cb]`` tile of a blocked map (the
    output of forward/dgrad, the cotangent operand of wgrad); ``pick`` ->
    (batch, channel_block, tile_h, tile_w).  For reduction-revisited outputs
    the picked indices must be constant along the reduction axes."""
    def index_map(*ids):
        b, c, th, tw = pick(*ids)
        return (b, c, th, tw, 0)

    return pl.BlockSpec((1, 1, hob, wob, cb), index_map)


def bias_spec(cob: int, pick: GridPick) -> pl.BlockSpec:
    """One ``[1, 1, Cob]`` bias pencil of a bias passed as ``[Co/Cob, 1,
    Cob]`` (``pencils``: a block's two minor dims must be whole or
    (8, 128)-aligned, and a ``[1, Cob]`` block of ``[Co/Cob, Cob]`` is
    neither once ``Co/Cob > 1``); ``pick`` -> (co_block,).  Also serves
    the fused bias-*gradient* output (``db``): its index map is constant
    along the wgrad reduction axes, so the flush-once revisit discipline
    applies."""
    def index_map(*ids):
        (co,) = pick(*ids)
        return (co, 0, 0)

    return pl.BlockSpec((1, 1, cob), index_map)


def pencils(b: jnp.ndarray) -> jnp.ndarray:
    """``[..., Co/Cob, Cob]`` -> ``[..., Co/Cob, 1, Cob]``: the kernel-side
    shape of a per-block pencil operand or output (bias, db, pooled GAP
    features), so its block is whole in the two minor dims."""
    return b.reshape(b.shape[:-1] + (1, b.shape[-1]))


def unpencil(out, flag: bool):
    """Undo :func:`pencils` on the pencil output of a ``(main, pencil)``
    launch result when ``flag`` (the launch had one)."""
    if not flag:
        return out
    main, pen = out
    return main, pen.reshape(pen.shape[:-2] + pen.shape[-1:])


def lane_pad(machine, interpret: bool, *pencils: int) -> Tuple[int, ...]:
    """Zero lanes each channel pencil gains for a compiled launch.

    Mosaic windows a block by element offset, and loads it with a stride,
    only when its minor dim fills whole lane tiles — so on the chip a
    narrow pencil (a first layer's Cb = 3, MobileNet's 32/64-channel
    maps) is zero-padded to the machine's lane tile for the launch and the
    pad is cropped off the result (:func:`crop_lanes`).  The model's
    widths and stored weights are unchanged; the padded lanes are zeros in
    and cropped out.  Interpret mode has no tiles and pads nothing."""
    if interpret or machine.tile is None:
        return (0,) * len(pencils)
    lanes = machine.tile[1]
    return tuple(-p % lanes for p in pencils)


def pad_lanes(t, k: int, axis: int = -1):
    """Zero-pad ``t`` (None passes through) by ``k`` at the end of
    ``axis``."""
    if t is None or not k:
        return t
    pad = [(0, 0)] * t.ndim
    pad[axis] = (0, k)
    return jnp.pad(t, pad)


def crop_lanes(out, cb: int, gap: bool, nblk: int):
    """Crop the lane pad of a padded launch's result back to ``cb`` lanes
    per pencil: a blocked map, or with ``gap`` the flat pooled
    ``[N, nblk * padded]`` features."""
    if gap:
        n = out.shape[0]
        if out.shape[1] == nblk * cb:
            return out
        return out.reshape(n, nblk, -1)[..., :cb].reshape(n, nblk * cb)
    return out if out.shape[-1] == cb else out[..., :cb]


def gap_spec(cob: int, pick: GridPick) -> pl.BlockSpec:
    """One ``[1, 1, 1, Cob]`` pooled-feature pencil of the fused GAP
    output, laid out ``[N, Co/Cob, 1, Cob]`` (see :func:`pencils`);
    ``pick`` -> (batch, co_block).  The index map is constant along the
    spatial-tile and reduction axes — the pooled block is revisited and
    written once by ``gap_update``'s last-tile guard."""
    def index_map(*ids):
        b, co = pick(*ids)
        return (b, co, 0, 0)

    return pl.BlockSpec((1, 1, 1, cob), index_map)


def tap_windows(x, hf: int, wf: int, hob: int, wob: int,
                stride: int = 1,
                dilation: Tuple[int, int] = (1, 1), dtype=None,
                lead: Tuple[int, ...] = (),
                ) -> Iterator[Tuple[Tuple[int, int], jnp.ndarray]]:
    """Yield ``((dh, dw), window[hob*wob, cb])`` for every filter tap.

    ``x`` is the resident ``[Hib, Wib, Cb]`` input patch, a VMEM ref (or,
    at stride 1, an already-loaded value); ``lead`` indexes the patch out
    of a ref with unit leading dims (a block ``[1, 1, Hib, Wib, Cb]`` is
    read as ``lead=(0, 0)`` — one indexer, never a ``.at`` view, whose
    unpadded shape Mosaic cannot slice for packed bf16).  Each window is a
    load straight out of it — these are the rows of the im2col matrix,
    never copied out of the already-resident patch.  Strided windows are
    strided *ref loads* (``pl.ds(..., stride=s)``): Mosaic lowers no
    strided slice of a loaded value, and its strided load takes 32-bit
    data only (see ``strided_source``).  ``dtype`` casts each window (the
    narrow operand dtype of a widened source).  The unrolled (dh, dw) loop
    is the paper's n, m loops (``Hf*Wf`` is small).  Tap ``(dh, dw)``
    starts at element offset ``(dh*dil_h, dw*dil_w)`` — the whole dilation
    story for forward kernels is this one stride on the tap origin.
    """
    cb = x.shape[-1]
    dil_h, dil_w = dilation
    for dh in range(hf):
        for dw in range(wf):
            oh, ow = dh * dil_h, dw * dil_w
            if stride == 1:
                win = x[lead + (slice(oh, oh + hob), slice(ow, ow + wob),
                                slice(None))]
            else:
                win = x[lead + (pl.ds(oh, hob, stride=stride),
                                pl.ds(ow, wob, stride=stride), slice(None))]
            if dtype is not None:
                win = win.astype(dtype)
            yield (dh, dw), win.reshape(hob * wob, cb)


def strided_source(ref, stride: int, body: Callable,
                   lead: Tuple[int, ...] = (0, 0)):
    """Run ``body(src, dtype, lead)`` with a tap source for the
    ``[Hib, Wib, Cb]`` patch ``ref[lead]`` that ``tap_windows`` can read at
    ``stride``.

    Mosaic's strided load takes 32-bit data only, so a strided launch over
    narrower (bf16) operands first widens the patch once into a scoped f32
    VMEM copy — exact, and the taps narrow each window back to the operand
    dtype before the MXU sees it (``tap_windows(dtype=...)``).  Stride-1
    and 32-bit launches read ``ref`` directly.  ``body`` gets
    ``(src, dtype, lead)``: the source, the dtype its windows are cast to
    and the leading index of the patch in it; its return value is
    returned.
    """
    if stride == 1 or ref.dtype.itemsize == 4:
        return body(ref, None, lead)

    def scoped(buf):
        buf[...] = ref[lead].astype(jnp.float32)
        return body(buf, ref.dtype, ())

    shape = ref.shape[len(lead):]
    return pl.run_scoped(scoped, pltpu.VMEM(shape, jnp.float32))


def compiler_params(machine, semantics: Sequence[str]):
    """The Mosaic parameters every launch of the family sets: the grid's
    ``dimension_semantics`` ("parallel" axes carry no cross-step state;
    "arbitrary" ones are reductions or revisit an output block) and the
    scoped-VMEM limit of the ``MachineModel`` the tiles were fitted
    against — so a tile the blocking model admits is also a tile the
    compiler may allocate (DESIGN.md §17).  A model without a limit (the
    CPU test machines) leaves the compiler's default."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(semantics),
        vmem_limit_bytes=machine.vmem_limit_bytes or None)


def forward_semantics(gap: bool, reduction: bool = True) -> Tuple[str, ...]:
    """``dimension_semantics`` of a forward grid ``(n, co, th, tw[, ci])``:
    a fused GAP revisits its pooled pencil across the spatial-tile axes,
    which makes them "arbitrary"; the channel reduction ``ci`` always is."""
    spatial = ("arbitrary",) * 2 if gap else ("parallel",) * 2
    return ("parallel", "parallel") + spatial + (
        ("arbitrary",) if reduction else ())


def wgrad_semantics(with_db: bool) -> Tuple[str, ...]:
    """``dimension_semantics`` of a weight-gradient grid ``(co, ci, n, th,
    tw)``: a fused ``db`` pencil is revisited across ``ci`` as well as
    across the reduction axes."""
    return ("parallel", "arbitrary" if with_db else "parallel",
            "arbitrary", "arbitrary", "arbitrary")


def first_step(axes: Sequence[int]):
    """True on the first iteration of the given reduction grid axes."""
    cond = pl.program_id(axes[0]) == 0
    for a in axes[1:]:
        cond &= pl.program_id(a) == 0
    return cond


def last_step(axes: Sequence[int]):
    """True on the last iteration of the given reduction grid axes."""
    cond = pl.program_id(axes[0]) == pl.num_programs(axes[0]) - 1
    for a in axes[1:]:
        cond &= pl.program_id(a) == pl.num_programs(a) - 1
    return cond


def epilogue_flush(o_ref, acc: jnp.ndarray, hob: int, wob: int,
                   b_ref=None, activation: Optional[str] = None,
                   r_ref=None) -> jnp.ndarray:
    """The single output store: bias + activation (+ residual skip-add) on
    the f32 accumulator, one down-cast write of the ``[hob, wob, cb]`` tile
    (DESIGN.md §5, §14).

    This is where the mixed-precision policy's accumulator guarantee is
    enforced: whatever the operand dtype (f32 or bf16), the tile arrives
    here as f32 partial sums and is cast to the output dtype exactly once —
    a bf16 run is never bf16-naive summation (DESIGN.md §10).

    ``r_ref`` is the fused residual tile (``out = act(z + bias) +
    residual``): the skip branch rides the flush, added in f32 *before* the
    single down-cast, so the fused chain re-streams zero extra HBM bytes
    and matches the two-pass reference exactly under the f32 policy.

    Returns the stored ``[hob, wob, cb]`` tile (output dtype) so further
    fused consumers — the GAP partial-sum rider — see exactly the values
    that were written.
    """
    assert acc.dtype == jnp.float32, (
        f"epilogue got a {acc.dtype} accumulator; the kernel scratch must "
        "stay f32 under every precision policy")
    out = acc
    if b_ref is not None:
        out = out + b_ref[0].astype(jnp.float32)         # (1, Cob) broadcast
    out = apply_activation(out, activation)
    cb = o_ref.shape[-1]
    if r_ref is not None:
        out = out.reshape(hob, wob, cb) + r_ref[0, 0].astype(jnp.float32)
    tile = out.reshape(hob, wob, cb).astype(o_ref.dtype)
    o_ref[0, 0] = tile
    return tile


def tree_sum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Balanced-tree sum along ``axis`` with a *fixed* association.

    ``jnp.sum`` lowers to an XLA reduce whose association is a codegen
    choice: the same reduce over the same values rounds differently
    depending on the fusion context around it (measured: the fused-gap
    kernel's in-body reduce vs the identical expression jitted standalone
    differ by 1 ulp).  This helper spends that freedom up front — an
    explicit halving tree of elementwise adds, each exact-rounded IEEE —
    so the result bits are a function of the values alone, in any program.
    ``gap_update`` sums tiles with it and the jnp impl replays the same
    tree (``nn.conv``), which is what keeps gap-fused convs inside
    ``EXACT_IMPLS`` (the serving tier's degraded path owes bit-identical
    logits — DESIGN.md §16).  Odd extents carry a zero pad; ``x + 0.0``
    is bit-exact for every finite value (only ``-0.0`` renormalizes).
    """
    while x.shape[axis] > 1:
        m = x.shape[axis]
        if m % 2:
            pad = [(0, 0)] * x.ndim
            pad[axis] = (0, 1)
            x = jnp.pad(x, pad)
            m += 1
        lo = jax.lax.slice_in_dim(x, 0, m // 2, axis=axis)
        hi = jax.lax.slice_in_dim(x, m // 2, m, axis=axis)
        x = lo + hi
    return jnp.squeeze(x, axis=axis)


def gap_update(g_ref, gacc_ref, tile: jnp.ndarray, hw: int,
               is_first, is_last) -> None:
    """Fold one flushed output tile into the fused global-average-pool.

    ``tile`` is what ``epilogue_flush`` just stored (output dtype — the
    pooled result must see the written values, like the two-pass reference
    that re-reads the map); its spatial sum accumulates in the persistent
    ``[1, cb]`` f32 scratch ``gacc_ref`` across the spatial tiles, and
    after the last tile the pooled pencil is scaled by the *full* spatial
    extent ``hw`` and written once to ``g_ref``.  Partial sums stay f32
    for the same reason the matmul accumulator does: per-tile rounding of
    a bf16 running mean would accumulate across tiles (DESIGN.md §14).

    The mean multiplies by a trace-time f32 reciprocal instead of
    dividing: a literal ``/ hw`` is rewritten to a reciprocal-multiply in
    some fusion contexts but kept a true divide in others (measured 1-ulp
    splits between the fused kernel and the identical expression jitted
    standalone), while an explicit multiply survives codegen bit-exactly —
    same reasoning as ``tree_sum``, and the jnp impl replays the same
    constant (``EXACT_IMPLS``, DESIGN.md §16).

    ``is_first``/``is_last`` are the caller's spatial-tile-axis guards
    (``first_step``/``last_step`` over the tile axes), passed in as values:
    this helper runs inside the flush's ``pl.when`` and ``pl.program_id``
    may not be issued inside a conditional body.
    """
    part = tree_sum(tile.astype(jnp.float32).reshape(-1, tile.shape[-1]),
                    axis=0)[None, :]                            # [1, cb]
    gacc_ref[...] = jnp.where(is_first, part, gacc_ref[...] + part)

    inv_hw = np.float32(1.0) / np.float32(hw)

    @pl.when(is_last)
    def _pool():
        g_ref[0, 0] = (gacc_ref[...] * inv_hw).astype(g_ref.dtype)


def cotangent_prologue(g: jnp.ndarray, z, activation: Optional[str],
                       ) -> jnp.ndarray:
    """``dz = g * act'(z)`` on tile load — the backward twin of the fused
    epilogue (DESIGN.md §14).

    ``g`` is the raw incoming cotangent tile (operand dtype), ``z`` the
    saved pre-activation tile (the policy's residual dtype).  The cast
    discipline reproduces the unfused XLA pointwise op bit for bit: the
    cotangent is taken at ``z``'s dtype, ``act'`` is evaluated in f32 via
    the activation's own VJP (no hand-derived derivative to drift), and
    the product is rounded back to ``z``'s dtype before returning at
    ``g``'s dtype — elementwise, so computing it per halo'd patch inside
    the kernel commutes with windowing, and the stride-dilated zero rows
    of a dgrad cotangent stay exactly zero (``0 * act'(0) = 0``).
    """
    if z is None or activation in (None, "linear"):
        return g
    zf = z.astype(jnp.float32)
    gf = g.astype(z.dtype).astype(jnp.float32)
    dz = jax.vjp(lambda t: apply_activation(t, activation), zf)[1](gf)[0]
    return dz.astype(z.dtype).astype(g.dtype)
