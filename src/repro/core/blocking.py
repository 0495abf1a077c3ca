"""Analytical blocking model (paper §3.1), and its TPU adaptation.

The paper derives the loop blocking from two inequalities:

  Eq. 1:  E >= N_vec * N_fma * L_fma     (enough independent outputs in flight)
  Eq. 2:  E <= N_reg * N_vec             (outputs must fit the register file)

with ``E = C_o,b * W_o,b`` the register-resident output tile.  On TPU the
"registers" are VMEM-resident accumulator tiles feeding the 128x128 MXU, so:

  * ``N_vec``  -> lane width 128 (C_o,b is the lane dim, exactly the paper's
                  "C_o,b is a multiple of the vector length").
  * ``N_fma * L_fma`` -> keeping the systolic array full: the M-dimension of
                  each per-offset matmul ([rows x Cib] @ [Cib x Cob]) should be
                  >= the sublane granule (8) and ideally >= 128 (one MXU pass).
  * ``N_reg``  -> VMEM capacity shared by the accumulator tile, the input
                  window and the weight tile.

``choose_blocking`` returns block sizes satisfying both adapted inequalities
plus the VMEM budget, preferring hardware-aligned shapes.  The pure-CPU model
(``cpu_min_tile_elems``) is kept verbatim for fidelity tests of Eq. 1/2.
"""
from __future__ import annotations

import dataclasses

from .convspec import as_dilation
from .errors import TransientError
from .layout import choose_pencil, divisors, largest_divisor_leq
from .precision import resolve_precision

__all__ = [
    "MachineModel", "TPU_V5E", "CPU_HASWELL", "tile_bytes", "Blocking", "StreamBlocking",
    "VmemMisfitError",
    "cpu_min_tile_elems", "cpu_max_tile_elems", "resident_bytes",
    "choose_blocking", "dgrad_extents", "choose_dgrad_blocking",
    "wgrad_resident_bytes", "choose_wgrad_blocking",
    "stream_resident_bytes", "choose_stream_blocking",
    "choose_stream_dgrad_blocking",
    "stream_wgrad_resident_bytes", "choose_stream_wgrad_blocking",
    "depthwise_resident_bytes", "choose_depthwise_blocking",
    "depthwise_wgrad_resident_bytes", "choose_depthwise_wgrad_blocking",
    "pointwise_resident_bytes", "choose_pointwise_blocking",
    "pointwise_wgrad_resident_bytes", "choose_pointwise_wgrad_blocking",
]


class VmemMisfitError(TransientError, ValueError):
    """A blocking model could not satisfy its VMEM inequality at the smallest
    admissible tile.  A distinct type (still a ``ValueError`` — existing
    callers and tests keep working) so the kernel router can tell a genuine
    capacity misfit — which the streamed halo-DMA variant may still serve —
    from an invalid-argument error, which must always propagate.  It also
    sits in the ``core.errors`` transient branch (DESIGN.md §16): a misfit
    is a capacity condition with a bit-identical degrade path, not a bug.
    """


def _policy_itemsizes(precision, in_dtype_bytes: int,
                      acc_dtype_bytes: int) -> tuple[int, int]:
    """Resolve the (operand, accumulator) itemsizes the VMEM inequality uses.

    A ``precision`` policy overrides the raw byte counts — this is the single
    place the mixed-precision policy meets the blocking model: bf16 operands
    halve the window/weight/output terms of the inequality (the accumulator
    term stays f32), so ``choose_blocking`` admits strictly larger (or equal)
    tiles for the same VMEM budget.
    """
    if precision is None:
        return in_dtype_bytes, acc_dtype_bytes
    pol = resolve_precision(precision)
    return pol.operand_itemsize, pol.accum_itemsize


@dataclasses.dataclass(frozen=True)
class MachineModel:
    name: str
    n_vec: int          # SIMD/lane width in elements (f32)
    n_fma: int          # FMA units (CPU) / MXU passes overlapped (TPU: 1)
    l_fma: int          # FMA latency (CPU) / min sublane granule (TPU: 8)
    n_reg: int          # registers (CPU) / VMEM budget in lane-rows (TPU)
    vmem_bytes: int = 0          # 0 for CPU models
    mxu: int = 128               # systolic dim (TPU)
    peak_flops: float = 0.0      # per-chip peak (bf16 for TPU)
    hbm_bw: float = 0.0          # bytes/s
    ici_bw: float = 0.0          # bytes/s per link
    vmem_limit_bytes: int = 0    # scoped-VMEM limit every launch sets
    tile: tuple | None = None    # VMEM memory tile (rows of 32-bit words,
                                 # lanes) blocks pad to; None = unpadded
    max_tile_rows: int = 0       # cap on a tile's matmul rows (hob*wob);
                                 # 0 = uncapped
    source: str = ""             # where the peaks above come from


# TPU v5e.  Peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over four links).  VMEM: the chip
# has 128 MiB; launches ask the compiler for ``vmem_limit_bytes`` of it and
# the blocking models fit their padded, double-buffered tiles into the
# smaller ``vmem_bytes`` — the rest is Mosaic's own room for the values a
# kernel body holds (tap windows, matmul results, the widened source of a
# strided bf16 launch).  Both numbers are set from what compiles for the
# chip (tests/test_tpu_compile.py, DESIGN.md §7).
TPU_V5E = MachineModel(
    name="tpu_v5e", n_vec=128, n_fma=1, l_fma=8, n_reg=512,
    vmem_bytes=48 * 2**20, mxu=128,
    peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
    vmem_limit_bytes=100 * 2**20, tile=(8, 128), max_tile_rows=2048,
    source='Google Cloud documentation, "TPU v5e"',
)

# Paper Table 1, Intel i7-4770K (Haswell): AVX2 (8 f32 lanes), 2 FMA units,
# latency 5, 16 logical ymm registers.
CPU_HASWELL = MachineModel(name="haswell", n_vec=8, n_fma=2, l_fma=5, n_reg=16)


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_bytes(shape, itemsize: int, tile=None) -> int:
    """VMEM bytes of one block.  ``tile = (rows, lanes)`` is the machine's
    memory tile (:attr:`MachineModel.tile`; v5e: 8 rows of 32-bit words by
    128 lanes): the minor dim pads to ``lanes``, the second-minor to
    ``rows`` words (8 rows of f32, 16 of bf16), leading dims stay as they
    are — so a narrow pencil costs what it really occupies.  ``tile=None``
    is the unpadded byte count (the paper's model; tiny test machines)."""
    shape = tuple(shape)
    n = 1
    for d in shape:
        n *= d
    if tile is None:
        return n * itemsize
    if len(shape) == 1:
        shape = (1,) + shape
    *lead, rows, lanes = shape
    n = 1
    for d in lead:
        n *= d
    return (n * _ceil(rows, tile[0] * max(1, 4 // itemsize))
            * _ceil(lanes, tile[1]) * itemsize)


def _widened(hib: int, wib: int, cb: int, stride: int,
             in_dtype_bytes: int, tile) -> int:
    """The scoped f32 copy a strided launch over sub-32-bit operands makes
    of its window on the chip (``kernels.conv2d_common.strided_source``);
    only a tiled (chip) model counts it."""
    if tile is None or stride == 1 or in_dtype_bytes >= 4:
        return 0
    return tile_bytes((hib, wib, cb), 4, tile)


def cpu_min_tile_elems(m: MachineModel) -> int:
    """Paper Eq. 1:  E >= N_vec * N_fma * L_fma."""
    return m.n_vec * m.n_fma * m.l_fma


def cpu_max_tile_elems(m: MachineModel) -> int:
    """Paper Eq. 2:  E <= N_reg * N_vec."""
    return m.n_reg * m.n_vec


@dataclasses.dataclass(frozen=True)
class Blocking:
    """Blocking parameters for Algorithm 3 (paper) / the Pallas grid (ours)."""
    cob: int    # output-channel pencil  (lane dim)
    cib: int    # input-channel block    (contraction depth per grid step)
    hob: int    # output rows per tile   (with wob, the matmul M dim)
    wob: int    # output cols per tile

    @property
    def tile_elems(self) -> int:
        return self.cob * self.hob * self.wob


def resident_bytes(hob: int, wob: int, cob: int, cib: int, hf: int, wf: int,
                   stride: int = 1, in_dtype_bytes: int = 4,
                   acc_dtype_bytes: int = 4, dilation=(1, 1),
                   fused_residual: bool = False, fused_gap: bool = False,
                   fused_prologue: bool = False, tile=None) -> int:
    """VMEM bytes one Pallas grid step holds resident (DESIGN.md §7):
    double-buffered halo'd input window, weight tile and output tile
    (Pallas pipelines all operand blocks), plus the persistent f32
    accumulator scratch.  The single source of the inequality
    ``choose_blocking`` fits against — benchmarks and tests must use this,
    not a copy.  ``dilation`` widens the halo: the window spans the
    *effective* filter extent ``(hf-1)*dh + 1`` while the weight tile stays
    ``hf x wf`` taps.

    The fused-epilogue/prologue riders (DESIGN.md §14) add their own
    resident blocks, all zero when the flags are off: ``fused_residual``
    pipelines one more out-tile-shaped operand (the skip branch),
    ``fused_gap`` adds the pooled ``[1, cob]`` output block plus its f32
    partial-sum scratch, and ``fused_prologue`` (backward only) pipelines
    the saved pre-activation ``z`` alongside the cotangent — window-shaped,
    because the dgrad kernel windows both identically."""
    dh, dw = as_dilation(dilation)
    hib = (hob - 1) * stride + (hf - 1) * dh + 1          # halo'd input rows
    wib = (wob - 1) * stride + (wf - 1) * dw + 1          # halo'd input cols
    win = tile_bytes((hib, wib, cib), in_dtype_bytes, tile)
    wgt = tile_bytes((hf, wf, cib, cob), in_dtype_bytes, tile)
    out = tile_bytes((hob, wob, cob), in_dtype_bytes, tile)     # output block
    acc = tile_bytes((hob * wob, cob), acc_dtype_bytes, tile)   # scratch (single)
    total = (2 * (win + wgt + out) + acc
             + _widened(hib, wib, cib, stride, in_dtype_bytes, tile))
    if fused_residual:
        total += 2 * out                                  # skip-branch tile
    if fused_gap:
        total += (2 * tile_bytes((1, cob), in_dtype_bytes, tile)
                  + tile_bytes((1, cob), acc_dtype_bytes, tile))
    if fused_prologue:
        total += 2 * win                                  # z rides with g
    return total


def _rows_ok(machine: MachineModel, rows: int) -> bool:
    """The tile-size ceiling next to Eq. 1's floor: Mosaic unrolls a kernel
    body over the tile's vregs, so its compile time grows with the tile
    while the per-step overhead it amortizes stops mattering well before a
    whole 112x112 map (``MachineModel.max_tile_rows``)."""
    return not machine.max_tile_rows or rows <= machine.max_tile_rows


def _shrink_to_fit(extent: int, cur: int, pinned: bool, fits) -> int:
    """Halve ``cur`` along divisors of ``extent`` until ``fits(cur)`` (or 1).

    The one shrink strategy every blocking model uses (forward and wgrad —
    they differ only in the ``fits`` predicate): next candidate is the
    largest divisor <= half the current tile, stopping at a fixed point.
    Pinned dims are never shrunk."""
    while not pinned and cur > 1 and not fits(cur):
        nxt = largest_divisor_leq(extent, max(1, cur // 2))
        if nxt == cur:
            break
        cur = nxt
    return cur


def choose_blocking(
    hi: int, wi: int, ci: int, co: int, hf: int, wf: int,
    stride: int = 1, machine: MachineModel = TPU_V5E,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    cob: int | None = None, cib: int | None = None,
    hob: int | None = None, wob: int | None = None,
    precision=None, groups: int = 1, dilation=(1, 1),
    fused_residual: bool = False, fused_gap: bool = False,
    fused_prologue: bool = False,
) -> Blocking:
    """Pick (Cob, Cib, Hob, Wob) per the adapted Eq. 1/2 + VMEM budget.

    The Pallas kernel holds, per grid step (DESIGN.md §4/§7):
      input window   hib*wib*cib         (hib = (hob-1)*stride + hf,
                                          wib = (wob-1)*stride + wf: the
                                          halo'd patch feeding one tile)
      weight tile    hf*wf*cib*cob
      acc tile       hob*wob*cob         (f32)
    All three must fit the VMEM budget; the output tile should satisfy the
    adapted Eq. 1 (>= one MXU pass of rows when possible).

    ``hob``/``wob`` are always divisors of ``ho``/``wo``: the kernel's
    overlapping input windows then never index past the input plane (the
    last tile's window ends exactly at ``(ho-1)*stride + hf - 1 <= hi - 1``
    and likewise in W), so no out-of-bounds padding semantics are ever
    relied on.

    Under VMEM pressure the model shrinks ``hob`` first (row tiling), then
    ``wob`` (the paper's W_o,b — column tiling, what makes the kernel
    shape-robust for wide maps), and only then falls back to shallower
    ``cib`` (the paper's cache-level Ci blocking).

    ``cob``/``cib`` pin the channel blocks to the caller's *actual* operand
    layout (the Pallas wrapper passes the pencil sizes baked into its
    arrays); the VMEM fit is then evaluated against the real block sizes,
    and a pinned ``cib`` is never shrunk (the kernel cannot re-block its
    operands).  ``hob``/``wob`` likewise pin an explicitly-requested spatial
    tile (must divide Ho/Wo): the free dim is then chosen *under* that
    constraint, so a caller fixing one dim still gets a fitting pair — or
    the model's clear error instead of a downstream VMEM allocation failure.

    ``precision`` (a ``core.precision.Precision`` or its name) overrides the
    raw ``in_dtype_bytes``/``acc_dtype_bytes``: bf16 operands halve every
    term of the inequality except the f32 accumulator, so the model admits
    larger (never smaller) tiles than the f32 fit for the same budget.

    ``groups`` makes the channel sizing block-diagonal: default pencils are
    chosen per group (``cib`` caps at ``ci // groups`` — the reduction a
    grouped kernel ever contracts is one group's input blocks), and a pinned
    pencil must divide the per-group channel count.  ``dilation`` widens the
    input-window term of the inequality (see :func:`resident_bytes`) and the
    output extents use the effective filter span.
    """
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    dil = as_dilation(dilation)
    hf_eff = (hf - 1) * dil[0] + 1
    wf_eff = (wf - 1) * dil[1] + 1
    ho = (hi - hf_eff) // stride + 1
    wo = (wi - wf_eff) // stride + 1
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty output for input {hi}x{wi}, filter {hf}x{wf}")
    if groups < 1 or ci % groups or co % groups:
        raise ValueError(f"groups={groups} must divide ci={ci} and co={co}")
    cig, cog = ci // groups, co // groups                 # per-group channels

    cib_pinned = cib is not None
    hob_pinned = hob is not None
    wob_pinned = wob is not None
    if cob is None:
        cob = choose_pencil(co, machine.n_vec, groups=groups)   # lane dim
    elif groups > 1 and cog % cob:
        raise ValueError(
            f"cob={cob} must divide the per-group output channels "
            f"{cog} (co={co}, groups={groups})")
    if cib is None:
        cib = choose_pencil(ci, machine.n_vec, groups=groups)   # contraction
    elif groups > 1 and cig % cib:
        raise ValueError(
            f"cib={cib} must divide the per-group input channels "
            f"{cig} (ci={ci}, groups={groups})")
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")

    # Adapted Eq.1: rows per matmul (hob*wob) >= l_fma granule, target mxu.
    min_rows = machine.l_fma
    # Full output map per tile is the default (one window slide covers the
    # whole map — zero halo traffic); shrink the tile only under VMEM
    # pressure.
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(cib_, hob_, wob_):
            return resident_bytes(hob_, wob_, cob, cib_, hf, wf, stride,
                                  in_dtype_bytes, acc_dtype_bytes,
                                  dilation=dil,
                                  fused_residual=fused_residual,
                                  fused_gap=fused_gap,
                                  fused_prologue=fused_prologue,
                                  tile=machine.tile,
                                  ) <= machine.vmem_bytes and _rows_ok(
                                      machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned,
                             lambda h: fits(cib, h, wob))
        # wide maps: tile columns too (2-D spatial blocking, paper Alg. 3's
        # W_o,b) before touching the contraction depth
        wob = _shrink_to_fit(wo, wob, wob_pinned,
                             lambda w: fits(cib, hob, w))
        # huge channel blocks: shallower contraction (the paper's cache-level
        # Ci blocking — per group: the kernel only ever contracts one group's
        # input blocks) until the resident window fits VMEM
        cib = _shrink_to_fit(cig, cib, cib_pinned,
                             lambda c: fits(c, hob, wob))
        if not fits(cib, hob, wob):
            raise VmemMisfitError(
                f"conv tile does not fit VMEM at hob={hob}, wob={wob}, "
                f"cib={cib} (pinned dims included): filter {hf}x{wf} with "
                f"cob={cob} needs more than {machine.vmem_bytes} bytes "
                f"resident.  The streamed halo-DMA variant "
                f"(kernels/conv2d_stream) holds only ~2 row-strips + a "
                f"singly-resident weight tile and may still serve this "
                f"shape: pass stream=True to the Pallas entry points, or "
                f"leave stream=None to auto-route through it")
        # Eq. 1 floor: grow the tile back to the smallest divisor pair that
        # still fits VMEM and yields >= min_rows matmul rows.
        if not hob_pinned and hob * wob < min_rows:
            for cand in divisors(ho):
                if (cand >= hob and cand * wob >= min_rows
                        and fits(cib, cand, wob)):
                    hob = cand
                    break
        if not wob_pinned and hob * wob < min_rows:
            for cand in divisors(wo):
                if (cand >= wob and hob * cand >= min_rows
                        and fits(cib, hob, cand)):
                    wob = cand
                    break
    return Blocking(cob=cob, cib=cib, hob=hob, wob=wob)


# ---------------------------------------------------------------------------
# Backward-pass tile sizing (DESIGN.md §9).  Both kernels are parameterized
# by the same Blocking vocabulary as the forward — the point of the shared
# grid machinery — but the quantities the inequality fits are different:
# dgrad convolves a *dilated, halo-padded cotangent* at stride 1 with the
# channel pencils swapped, and wgrad holds a whole [Hf, Wf, Cib, Cob]
# accumulator resident across its three reduction axes.
# ---------------------------------------------------------------------------

def dgrad_extents(ho: int, wo: int, hf: int, wf: int,
                  stride: int = 1, dilation=(1, 1)) -> tuple[int, int]:
    """Spatial extents of the dgrad kernel's output: the input-gradient rows
    a VALID forward conv ever touched, ``E = (out - 1) * stride + filter``
    with the *effective* (dilated) filter extent (trailing rows of the
    padded input beyond E have zero gradient).  The depthwise dgrad no
    longer uses it: it writes the unpadded input's own extents."""
    dh, dw = as_dilation(dilation)
    return ((ho - 1) * stride + (hf - 1) * dh + 1,
            (wo - 1) * stride + (wf - 1) * dw + 1)


def choose_dgrad_blocking(
    ho: int, wo: int, ci: int, co: int, hf: int, wf: int,
    stride: int = 1, machine: MachineModel = TPU_V5E,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    cib: int | None = None, cob: int | None = None,
    hob: int | None = None, wob: int | None = None,
    precision=None, groups: int = 1, dilation=(1, 1),
    fused_prologue: bool = False,
) -> Blocking:
    """Tile the transposed-window dgrad kernel (input gradient).

    dgrad is itself a blocked direct convolution — of the stride-dilated,
    ``(Hf-1)``-halo-padded cotangent against the 180°-mirrored filter, at
    stride 1, with the channel roles swapped (``Cib`` becomes the lane/output
    pencil, ``Cob`` the contraction depth).  So the §3 inequality applies
    verbatim to the transposed problem; this wrapper just states the
    transposition once:

      * output extent per dim is ``E = (out-1)*stride + filter``
        (:func:`dgrad_extents`) — the returned ``hob``/``wob`` divide E;
      * the window the kernel holds is ``(hob + hf - 1) x (wob + wf - 1)``
        of the *dilated* cotangent (stride-1 halo);
      * ``cob``/``cib`` of the returned Blocking are the input-channel /
        output-channel pencils respectively (swapped vs forward).

    ``cib``/``cob`` pin the pencils baked into the caller's operand layouts
    (x's channel block / w's output pencil).  ``precision`` has the forward
    model's meaning (bf16 cotangent windows halve the inequality).
    ``groups``/``dilation`` transpose with the problem: the dgrad of a
    grouped conv is grouped the same way (channel roles swapped within each
    group) and its taps stay dilation-strided over the padded cotangent.
    """
    dh, dw = as_dilation(dilation)
    eh, ew = dgrad_extents(ho, wo, hf, wf, stride, (dh, dw))
    return choose_blocking(
        eh + (hf - 1) * dh, ew + (wf - 1) * dw, co, ci, hf, wf, stride=1,
        machine=machine, in_dtype_bytes=in_dtype_bytes,
        acc_dtype_bytes=acc_dtype_bytes,
        cob=cib, cib=cob, hob=hob, wob=wob, precision=precision,
        groups=groups, dilation=(dh, dw), fused_prologue=fused_prologue)


def wgrad_resident_bytes(hob: int, wob: int, cob: int, cib: int,
                         hf: int, wf: int, stride: int = 1,
                         in_dtype_bytes: int = 4,
                         acc_dtype_bytes: int = 4, dilation=(1, 1),
                         fused_prologue: bool = False,
                         fused_bias: bool = False, tile=None) -> int:
    """VMEM bytes one wgrad grid step holds resident (DESIGN.md §9).

    Same double-buffered operand accounting as :func:`resident_bytes`, but
    the output block is the full ``[Hf, Wf, Cib, Cob]`` weight-gradient tile
    and the persistent f32 accumulator matches it — ``Hf*Wf`` times larger
    than the forward's ``[hob*wob, Cob]`` scratch, which is what changes the
    inequality.

    ``fused_prologue`` pipelines the saved pre-activation ``z`` tile next to
    the cotangent (the in-kernel ``dz = g * act'(z)``); ``fused_bias`` adds
    the flush-once ``db`` pencil output plus its f32 scratch (DESIGN.md
    §14).  Both are zero when off."""
    dh, dw = as_dilation(dilation)
    hib = (hob - 1) * stride + (hf - 1) * dh + 1
    wib = (wob - 1) * stride + (wf - 1) * dw + 1
    win = tile_bytes((hib, wib, cib), in_dtype_bytes, tile)     # x window (halo'd)
    cot = tile_bytes((hob, wob, cob), in_dtype_bytes, tile)     # cotangent tile
    wgt = tile_bytes((hf, wf, cib, cob), in_dtype_bytes, tile)  # dw output block
    acc = tile_bytes((hf, wf, cib, cob), acc_dtype_bytes, tile)  # scratch (single)
    total = (2 * (win + cot + wgt) + acc
             + _widened(hib, wib, cib, stride, in_dtype_bytes, tile))
    if fused_prologue:
        total += 2 * cot                                  # z rides with g
    if fused_bias:
        total += 3 * tile_bytes((1, cob), acc_dtype_bytes, tile)
    return total


def choose_wgrad_blocking(
    ho: int, wo: int, hf: int, wf: int, stride: int = 1,
    machine: MachineModel = TPU_V5E,
    cob: int = 128, cib: int = 128,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    hob: int | None = None, wob: int | None = None,
    precision=None, dilation=(1, 1),
    fused_prologue: bool = False, fused_bias: bool = False,
) -> Blocking:
    """Tile the per-tile accumulating wgrad kernel (weight gradient).

    wgrad reduces over the ``(N, Ho/Hob, Wo/Wob)`` grid axes into one
    resident ``[Hf, Wf, Cib, Cob]`` accumulator per ``(Co, Ci)`` block pair,
    so only the spatial tile is free: ``cob``/``cib`` are always pinned by
    the operand layouts (there is nothing to shrink — the accumulator *is*
    the output block).  Under VMEM pressure the model shrinks ``hob`` then
    ``wob`` (divisors of Ho/Wo, exactly the forward's constraint, since the
    cotangent tile and the halo'd x window tile the same output grid); a
    configuration that misfits even at ``hob = wob = 1`` raises.
    ``precision`` overrides the operand itemsize (the ``[Hf, Wf, Cib, Cob]``
    accumulator term stays f32 — it dominates this inequality, which is why
    bf16's wgrad win is smaller than forward's).
    """
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty cotangent {ho}x{wo}")
    hob_pinned, wob_pinned = hob is not None, wob is not None
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(hob_, wob_):
            return wgrad_resident_bytes(
                hob_, wob_, cob, cib, hf, wf, stride,
                in_dtype_bytes, acc_dtype_bytes,
                dilation=dilation, fused_prologue=fused_prologue,
                fused_bias=fused_bias,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned, lambda h: fits(h, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(hob, w))
        if not fits(hob, wob):
            raise VmemMisfitError(
                f"wgrad tile does not fit VMEM at hob={hob}, wob={wob}: "
                f"the [{hf}x{wf}x{cib}x{cob}] accumulator plus windows needs "
                f"more than {machine.vmem_bytes} bytes resident.  The "
                f"streamed wgrad variant (kernels/conv2d_stream) drops the "
                f"double-buffered windows and the VMEM output block (the "
                f"accumulator flushes by manual DMA) and may still fit: pass "
                f"stream=True to direct_conv2d_wgrad_pallas, or leave "
                f"stream=None to auto-route through it")
    return Blocking(cob=cob, cib=cib, hob=hob, wob=wob)


# ---------------------------------------------------------------------------
# Streamed (halo-DMA) tile sizing — DESIGN.md §11.  The streamed kernels do
# not let BlockSpec windows pull the whole halo'd patch: the input stays in
# HBM and a manually double-buffered ``make_async_copy`` pipeline streams it
# through a 2-slot ring of row-strips, while the weight tile is DMA'd once
# per grid step into singly-resident scratch.  That changes the inequality in
# two ways: the 2x on the weight tile disappears (the dominant term for deep
# pinned pencils), and the input term shrinks from the full window to two
# strips — with the *strip height* ``hso`` as a new free variable.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamBlocking:
    """Blocking for the streamed kernels: the window vocabulary plus ``hso``,
    the output rows per streamed strip (``hso`` divides ``hob`` divides Ho).
    ``hob`` is the rows one *grid step* accumulates (the acc/output tile);
    within a step the input band arrives as ``hob/hso`` ring strips whose
    ``Hf - stride`` row overlap is fetched from HBM exactly once."""
    cob: int    # output-channel pencil (lane dim)
    cib: int    # input-channel block  (contraction depth per grid step)
    hob: int    # output rows per grid step (the accumulator tile)
    wob: int    # output cols per tile
    hso: int    # output rows per streamed strip (ring granularity)

    @property
    def n_strips(self) -> int:
        return self.hob // self.hso


def stream_resident_bytes(hso: int, hob: int, wob: int, cob: int, cib: int,
                          hf: int, wf: int, stride: int = 1,
                          in_dtype_bytes: int = 4,
                          acc_dtype_bytes: int = 4,
                          fused_residual: bool = False,
                          fused_gap: bool = False, tile=None) -> int:
    """VMEM bytes one streamed fwd/dgrad grid step holds resident:

        weights   hf*wf*cib*cob       x1  (manual DMA into scratch — the
                                           streamed variant's headline win:
                                           no Pallas double-buffering)
        ring      2 * hin*wib*cib         (hin = (hso-1)*stride + hf: two
                                           strip slots, halo rows included)
        out tile  2 * hob*wob*cob         (a regular pipelined BlockSpec)
        acc       hob*wob*cob             (persistent f32 scratch)

    The single source of the streamed inequality — the router, tests and
    benchmarks must use this, not a copy.

    ``fused_residual`` adds one more pipelined out-tile-shaped operand (the
    skip branch rides the Pallas pipeline next to the output block, not the
    manual ring — it is only touched at the flush); ``fused_gap`` adds the
    pooled pencil output plus its f32 partial-sum scratch (DESIGN.md §14)."""
    hin = (hso - 1) * stride + hf
    wib = (wob - 1) * stride + wf
    wgt = tile_bytes((hf, wf, cib, cob), in_dtype_bytes, tile)
    ring = tile_bytes((2, hin, wib, cib), in_dtype_bytes, tile)
    out = 2 * tile_bytes((hob, wob, cob), in_dtype_bytes, tile)
    acc = tile_bytes((hob * wob, cob), acc_dtype_bytes, tile)
    total = (wgt + ring + out + acc
             + _widened(hin, wib, cib, stride, in_dtype_bytes, tile))
    if fused_residual:
        total += out
    if fused_gap:
        total += (2 * tile_bytes((1, cob), in_dtype_bytes, tile)
                  + tile_bytes((1, cob), acc_dtype_bytes, tile))
    return total


def choose_stream_blocking(
    hi: int, wi: int, ci: int, co: int, hf: int, wf: int,
    stride: int = 1, machine: MachineModel = TPU_V5E,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    cob: int | None = None, cib: int | None = None,
    hob: int | None = None, wob: int | None = None,
    hso: int | None = None,
    precision=None,
    fused_residual: bool = False, fused_gap: bool = False,
) -> StreamBlocking:
    """Tile the streamed forward kernel (and, transposed, its dgrad).

    Same contract as :func:`choose_blocking` — ``cob``/``cib`` pin the
    operand pencils, ``hob``/``wob`` must divide Ho/Wo, ``precision`` is the
    dtype-aware itemsize — plus the strip height ``hso`` (must divide
    ``hob``).  Defaults maximize reuse: the whole output map in one grid
    step (``hob = Ho``, ``wob = Wo``) streamed as one strip.  Under VMEM
    pressure the model shrinks, in order:

      1. ``hso`` — the ring shrinks; halo traffic is *unchanged* (strips
         share their overlap rows through the ring, so a band costs one
         fetch of its extent no matter how finely it is striped);
      2. ``hob`` — the accumulator/output tile shrinks; row-halo re-fetch
         appears at the new band seams (``bytes_halo_refetch``);
      3. ``wob`` — column tiling, the last resort (column halo re-fetch).

    A shape that misfits even at ``hso = hob = wob = 1`` raises
    :class:`VmemMisfitError`: the hard floor is the singly-resident weight
    tile plus two minimal strips — below that, no streaming helps."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty output for input {hi}x{wi}, filter {hf}x{wf}")

    hob_pinned = hob is not None
    wob_pinned = wob is not None
    hso_pinned = hso is not None
    if cob is None:
        cob = choose_pencil(co, machine.n_vec)
    if cib is None:
        cib = choose_pencil(ci, machine.n_vec)
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if hso_pinned and (hso < 1 or hob % hso):
        # hso | hob | Ho, so a pinned strip height must divide the band
        # (and hence Ho when the band defaults to the full extent)
        raise ValueError(f"hso={hso} must divide hob={hob}")
    if not wob_pinned:
        wob = wo
    if not hso_pinned:
        hso = hob

    if machine.vmem_bytes:
        def fits(hso_, hob_, wob_):
            return stream_resident_bytes(
                hso_, hob_, wob_, cob, cib, hf, wf, stride,
                in_dtype_bytes, acc_dtype_bytes,
                fused_residual=fused_residual,
                fused_gap=fused_gap,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hso = _shrink_to_fit(hob, hso, hso_pinned,
                             lambda s: fits(s, hob, wob))
        # ring is minimal; if the acc/out tile is what misfits, shrink the
        # band (hso follows down so it keeps dividing hob)
        while not hob_pinned and hob > 1 and not fits(hso, hob, wob):
            if hso_pinned:
                # the band must stay a multiple of the pinned strip height
                cand = [d for d in divisors(ho) if d < hob and d % hso == 0]
                nxt = max(cand) if cand else hob
            else:
                nxt = largest_divisor_leq(ho, max(1, hob // 2))
            if nxt == hob:
                break
            hob = nxt
            if not hso_pinned:
                hso = largest_divisor_leq(hob, hso)
        wob = _shrink_to_fit(wo, wob, wob_pinned,
                             lambda w: fits(hso, hob, w))
        if not fits(hso, hob, wob):
            raise VmemMisfitError(
                f"streamed conv tile does not fit VMEM at hso={hso}, "
                f"hob={hob}, wob={wob}, cib={cib} (pinned dims included): "
                f"even the streamed floor — the single [{hf}x{wf}x{cib}x"
                f"{cob}] weight tile plus two minimal strips — needs more "
                f"than {machine.vmem_bytes} bytes resident")
    return StreamBlocking(cob=cob, cib=cib, hob=hob, wob=wob, hso=hso)


def choose_stream_dgrad_blocking(
    ho: int, wo: int, ci: int, co: int, hf: int, wf: int,
    stride: int = 1, machine: MachineModel = TPU_V5E,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    cib: int | None = None, cob: int | None = None,
    hob: int | None = None, wob: int | None = None,
    hso: int | None = None,
    precision=None,
) -> StreamBlocking:
    """Streamed tiles for the transposed-window dgrad: exactly
    :func:`choose_dgrad_blocking`'s transposition (stride-1 windows over the
    dilated, ``Hf-1``-halo-padded cotangent, channel pencils swapped)
    applied to the streamed inequality.  The returned ``hob``/``hso``
    stripe the dgrad extents ``E = (out-1)*stride + filter``."""
    eh, ew = dgrad_extents(ho, wo, hf, wf, stride)
    return choose_stream_blocking(
        eh + hf - 1, ew + wf - 1, co, ci, hf, wf, stride=1,
        machine=machine, in_dtype_bytes=in_dtype_bytes,
        acc_dtype_bytes=acc_dtype_bytes,
        cob=cib, cib=cob, hob=hob, wob=wob, hso=hso, precision=precision)


def stream_wgrad_resident_bytes(hso: int, wob: int, cob: int, cib: int,
                                hf: int, wf: int, stride: int = 1,
                                in_dtype_bytes: int = 4,
                                acc_dtype_bytes: int = 4, tile=None) -> int:
    """VMEM bytes one streamed wgrad grid step holds resident.

    Both operands stream (a halo'd x ring and a disjoint cotangent ring);
    the ``[Hf, Wf, Cib, Cob]`` f32 accumulator is the only weight-sized
    buffer — it flushes to HBM by manual DMA, so the window path's
    double-buffered VMEM output block simply does not exist:

        2*(hin*wib*cib + hso*wob*cob)*in_bytes + hf*wf*cib*cob*acc_bytes
    """
    hin = (hso - 1) * stride + hf
    wib = (wob - 1) * stride + wf
    rings = (tile_bytes((2, hin, wib, cib), in_dtype_bytes, tile)
             + tile_bytes((2, hso, wob, cob), in_dtype_bytes, tile))
    acc = tile_bytes((hf, wf, cib, cob), acc_dtype_bytes, tile)
    return rings + acc + _widened(hin, wib, cib, stride, in_dtype_bytes, tile)


def choose_stream_wgrad_blocking(
    ho: int, wo: int, hf: int, wf: int, stride: int = 1,
    machine: MachineModel = TPU_V5E,
    cob: int = 128, cib: int = 128,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    wob: int | None = None, hso: int | None = None,
    precision=None,
) -> StreamBlocking:
    """Tile the streamed wgrad kernel.

    The channel pencils are pinned by the operand layouts (the accumulator
    *is* the weight block, exactly the window wgrad's contract) and the
    whole row extent streams in one grid step (``hob = Ho`` always — strips
    make row tiling at the grid level pointless here, since the accumulator
    does not grow with the band).  Free variables are ``hso`` (divides Ho)
    and ``wob`` (divides Wo); shrink order ``hso`` then ``wob``; a misfit at
    ``hso = wob = 1`` raises :class:`VmemMisfitError` — the floor is the
    f32 weight-gradient accumulator itself."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty cotangent {ho}x{wo}")
    wob_pinned, hso_pinned = wob is not None, hso is not None
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if hso_pinned and (hso < 1 or ho % hso):
        raise ValueError(f"hso={hso} must divide Ho={ho}")
    if not wob_pinned:
        wob = wo
    if not hso_pinned:
        hso = ho

    if machine.vmem_bytes:
        def fits(hso_, wob_):
            return stream_wgrad_resident_bytes(
                hso_, wob_, cob, cib, hf, wf, stride,
                in_dtype_bytes, acc_dtype_bytes,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hso_ * wob_)

        hso = _shrink_to_fit(ho, hso, hso_pinned, lambda s: fits(s, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(hso, w))
        if not fits(hso, wob):
            raise VmemMisfitError(
                f"streamed wgrad tile does not fit VMEM at hso={hso}, "
                f"wob={wob}: the irreducible [{hf}x{wf}x{cib}x{cob}] f32 "
                f"accumulator plus two minimal strips needs more than "
                f"{machine.vmem_bytes} bytes resident")
    return StreamBlocking(cob=cob, cib=cib, hob=ho, wob=wob, hso=hso)


# ---------------------------------------------------------------------------
# Depthwise tile sizing (DESIGN.md §13).  A depthwise conv contracts nothing:
# each lane of the channel pencil is its own group, so the "weight tile" is a
# [Hf, Wf, Cb] tap stack (no Cib x Cob matrix) and the kernel is VPU
# multiply-accumulate over taps.  The inequality is the window inequality
# with the weight term collapsed by a factor of Cb.
# ---------------------------------------------------------------------------

def depthwise_resident_bytes(hob: int, wob: int, cb: int, hf: int, wf: int,
                             stride: int = 1, in_dtype_bytes: int = 4,
                             acc_dtype_bytes: int = 4,
                             dilation=(1, 1),
                             fused_residual: bool = False,
                             fused_gap: bool = False,
                             fused_prologue: bool = False, tile=None) -> int:
    """VMEM bytes one depthwise grid step holds resident: double-buffered
    halo'd window, [Hf, Wf, Cb] tap stack and output tile, plus the f32
    accumulator.  The fused riders (residual tile / GAP pencil + scratch /
    backward ``z`` window) follow :func:`resident_bytes`."""
    dh, dw = as_dilation(dilation)
    hib = (hob - 1) * stride + (hf - 1) * dh + 1
    wib = (wob - 1) * stride + (wf - 1) * dw + 1
    win = tile_bytes((hib, wib, cb), in_dtype_bytes, tile)
    wgt = tile_bytes((hf, wf, 1, cb), in_dtype_bytes, tile)
    out = tile_bytes((hob, wob, cb), in_dtype_bytes, tile)
    acc = tile_bytes((hob * wob, cb), acc_dtype_bytes, tile)
    total = (2 * (win + wgt + out) + acc
             + _widened(hib, wib, cb, stride, in_dtype_bytes, tile))
    if fused_residual:
        total += 2 * out
    if fused_gap:
        total += (2 * tile_bytes((1, cb), in_dtype_bytes, tile)
                  + tile_bytes((1, cb), acc_dtype_bytes, tile))
    if fused_prologue:
        total += 2 * win
    return total


def choose_depthwise_blocking(
    hi: int, wi: int, c: int, hf: int, wf: int, stride: int = 1,
    machine: MachineModel = TPU_V5E, cb: int | None = None,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    hob: int | None = None, wob: int | None = None,
    precision=None, dilation=(1, 1),
    fused_residual: bool = False, fused_gap: bool = False,
    fused_prologue: bool = False,
) -> Blocking:
    """Tile the depthwise forward kernel (and, over the padded cotangent at
    stride 1, its dgrad).  The channel pencil ``cb`` is pinned by the
    operand layout (``cob == cib == cb`` in the returned Blocking); under
    VMEM pressure only the spatial tile shrinks, ``hob`` then ``wob``,
    divisors of Ho/Wo as everywhere else."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    dil = as_dilation(dilation)
    ho = (hi - ((hf - 1) * dil[0] + 1)) // stride + 1
    wo = (wi - ((wf - 1) * dil[1] + 1)) // stride + 1
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty output for input {hi}x{wi}, filter {hf}x{wf}")
    if cb is None:
        cb = choose_pencil(c, machine.n_vec)
    hob_pinned, wob_pinned = hob is not None, wob is not None
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(hob_, wob_):
            return depthwise_resident_bytes(
                hob_, wob_, cb, hf, wf, stride, in_dtype_bytes,
                acc_dtype_bytes, dilation=dil,
                fused_residual=fused_residual, fused_gap=fused_gap,
                fused_prologue=fused_prologue,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned, lambda h: fits(h, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(hob, w))
        if not fits(hob, wob):
            raise VmemMisfitError(
                f"depthwise tile does not fit VMEM at hob={hob}, wob={wob}, "
                f"cb={cb}: filter {hf}x{wf} needs more than "
                f"{machine.vmem_bytes} bytes resident")
    return Blocking(cob=cb, cib=cb, hob=hob, wob=wob)


def depthwise_wgrad_resident_bytes(hob: int, wob: int, cb: int,
                                   hf: int, wf: int, stride: int = 1,
                                   in_dtype_bytes: int = 4,
                                   acc_dtype_bytes: int = 4,
                                   dilation=(1, 1),
                                   fused_prologue: bool = False,
                                   fused_bias: bool = False, tile=None) -> int:
    """Depthwise wgrad residency: halo'd x window, cotangent tile, and the
    per-channel [Hf*Wf, Cb] tap-gradient accumulator.  With ``fused_prologue``
    the saved pre-activation ``z`` tile rides next to the cotangent; with
    ``fused_bias`` a [1, Cb] db output block plus its f32 scratch stay
    resident."""
    dh, dw = as_dilation(dilation)
    hib = (hob - 1) * stride + (hf - 1) * dh + 1
    wib = (wob - 1) * stride + (wf - 1) * dw + 1
    win = tile_bytes((hib, wib, cb), in_dtype_bytes, tile)
    cot = tile_bytes((hob, wob, cb), in_dtype_bytes, tile)
    wgt = tile_bytes((hf, wf, 1, cb), in_dtype_bytes, tile)
    acc = tile_bytes((hf * wf, cb), acc_dtype_bytes, tile)
    total = (2 * (win + cot + wgt) + acc
             + _widened(hib, wib, cb, stride, in_dtype_bytes, tile))
    if fused_prologue:
        total += 2 * cot
    if fused_bias:
        total += 3 * tile_bytes((1, cb), acc_dtype_bytes, tile)
    return total


def choose_depthwise_wgrad_blocking(
    ho: int, wo: int, hf: int, wf: int, stride: int = 1,
    machine: MachineModel = TPU_V5E, cb: int = 128,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    hob: int | None = None, wob: int | None = None,
    precision=None, dilation=(1, 1),
    fused_prologue: bool = False, fused_bias: bool = False,
) -> Blocking:
    """Tile the depthwise wgrad kernel: the [Hf*Wf, Cb] accumulator is tiny,
    so this almost always returns the full map; the shrink loop exists for
    the pathological machines the tests probe."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty cotangent {ho}x{wo}")
    hob_pinned, wob_pinned = hob is not None, wob is not None
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(hob_, wob_):
            return depthwise_wgrad_resident_bytes(
                hob_, wob_, cb, hf, wf, stride, in_dtype_bytes,
                acc_dtype_bytes, dilation=dilation,
                fused_prologue=fused_prologue,
                fused_bias=fused_bias,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned, lambda h: fits(h, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(hob, w))
        if not fits(hob, wob):
            raise VmemMisfitError(
                f"depthwise wgrad tile does not fit VMEM at hob={hob}, "
                f"wob={wob}, cb={cb}: needs more than {machine.vmem_bytes} "
                f"bytes resident")
    return Blocking(cob=cb, cib=cb, hob=hob, wob=wob)


# ---------------------------------------------------------------------------
# Pointwise (1x1) tile sizing.  No halo, no taps: the conv is a channel
# matmul per spatial tile, so the window term collapses to the tile itself
# and the weight tile is a plain [Cib, Cob] matrix.
# ---------------------------------------------------------------------------

def pointwise_resident_bytes(hob: int, wob: int, cob: int, cib: int,
                             in_dtype_bytes: int = 4,
                             acc_dtype_bytes: int = 4,
                             fused_residual: bool = False,
                             fused_gap: bool = False,
                             fused_prologue: bool = False, tile=None) -> int:
    """VMEM bytes one pointwise grid step holds resident: double-buffered
    input tile, [Cib, Cob] weight matrix and output tile, plus the f32
    accumulator.  Fused riders follow :func:`resident_bytes`; for the dgrad
    flavor ``fused_prologue`` adds the ``z`` tile pipelined next to the
    incoming cotangent."""
    xin = tile_bytes((hob, wob, cib), in_dtype_bytes, tile)
    wgt = tile_bytes((cib, cob), in_dtype_bytes, tile)
    out = tile_bytes((hob, wob, cob), in_dtype_bytes, tile)
    acc = tile_bytes((hob * wob, cob), acc_dtype_bytes, tile)
    total = 2 * (xin + wgt + out) + acc
    if fused_residual:
        total += 2 * out
    if fused_gap:
        total += (2 * tile_bytes((1, cob), in_dtype_bytes, tile)
                  + tile_bytes((1, cob), acc_dtype_bytes, tile))
    if fused_prologue:
        total += 2 * xin
    return total


def choose_pointwise_blocking(
    hi: int, wi: int, ci: int, co: int,
    machine: MachineModel = TPU_V5E,
    cob: int | None = None, cib: int | None = None,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    hob: int | None = None, wob: int | None = None,
    precision=None,
    fused_residual: bool = False, fused_gap: bool = False,
    fused_prologue: bool = False,
) -> Blocking:
    """Tile the 1x1-as-matmul kernel (forward, and dgrad with the channel
    pencils swapped by the caller).  Output extents equal input extents
    (stride 1, no pads — the pointwise feasibility gate); shrink order is
    ``hob`` -> ``wob`` -> ``cib``, the window model's order minus the halo
    terms that no longer exist."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    ho, wo = hi, wi
    cib_pinned = cib is not None
    hob_pinned, wob_pinned = hob is not None, wob is not None
    if cob is None:
        cob = choose_pencil(co, machine.n_vec)
    if cib is None:
        cib = choose_pencil(ci, machine.n_vec)
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(cib_, hob_, wob_):
            return pointwise_resident_bytes(
                hob_, wob_, cob, cib_, in_dtype_bytes,
                acc_dtype_bytes, fused_residual=fused_residual,
                fused_gap=fused_gap,
                fused_prologue=fused_prologue,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned, lambda h: fits(cib, h, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(cib, hob, w))
        cib = _shrink_to_fit(ci, cib, cib_pinned, lambda c: fits(c, hob, wob))
        if not fits(cib, hob, wob):
            raise VmemMisfitError(
                f"pointwise tile does not fit VMEM at hob={hob}, wob={wob}, "
                f"cib={cib}, cob={cob}: needs more than {machine.vmem_bytes} "
                f"bytes resident")
    return Blocking(cob=cob, cib=cib, hob=hob, wob=wob)


def pointwise_wgrad_resident_bytes(hob: int, wob: int, cob: int, cib: int,
                                   in_dtype_bytes: int = 4,
                                   acc_dtype_bytes: int = 4,
                                   fused_prologue: bool = False,
                                   fused_bias: bool = False, tile=None) -> int:
    """Pointwise wgrad residency: x tile, cotangent tile, and the [Cib, Cob]
    weight-gradient block + matching f32 accumulator.  ``fused_prologue``
    adds the saved ``z`` tile, ``fused_bias`` the [1, Cob] db block plus
    its f32 scratch."""
    xin = tile_bytes((hob, wob, cib), in_dtype_bytes, tile)
    cot = tile_bytes((hob, wob, cob), in_dtype_bytes, tile)
    wgt = tile_bytes((cib, cob), in_dtype_bytes, tile)
    acc = tile_bytes((cib, cob), acc_dtype_bytes, tile)
    total = 2 * (xin + cot + wgt) + acc
    if fused_prologue:
        total += 2 * cot
    if fused_bias:
        total += 3 * tile_bytes((1, cob), acc_dtype_bytes, tile)
    return total


def choose_pointwise_wgrad_blocking(
    ho: int, wo: int, machine: MachineModel = TPU_V5E,
    cob: int = 128, cib: int = 128,
    in_dtype_bytes: int = 4, acc_dtype_bytes: int = 4,
    hob: int | None = None, wob: int | None = None,
    precision=None,
    fused_prologue: bool = False, fused_bias: bool = False,
) -> Blocking:
    """Tile the pointwise wgrad kernel: pencils pinned by the operand
    layouts (the [Cib, Cob] accumulator is the output block), spatial tile
    shrinks ``hob`` -> ``wob`` under pressure."""
    in_dtype_bytes, acc_dtype_bytes = _policy_itemsizes(
        precision, in_dtype_bytes, acc_dtype_bytes)
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty cotangent {ho}x{wo}")
    hob_pinned, wob_pinned = hob is not None, wob is not None
    if hob_pinned and (hob < 1 or ho % hob):
        raise ValueError(f"hob={hob} must divide Ho={ho}")
    if wob_pinned and (wob < 1 or wo % wob):
        raise ValueError(f"wob={wob} must divide Wo={wo}")
    if not hob_pinned:
        hob = ho
    if not wob_pinned:
        wob = wo

    if machine.vmem_bytes:
        def fits(hob_, wob_):
            return pointwise_wgrad_resident_bytes(
                hob_, wob_, cob, cib, in_dtype_bytes,
                acc_dtype_bytes, fused_prologue=fused_prologue,
                fused_bias=fused_bias,
                tile=machine.tile) <= machine.vmem_bytes and _rows_ok(
                    machine, hob_ * wob_)

        hob = _shrink_to_fit(ho, hob, hob_pinned, lambda h: fits(h, wob))
        wob = _shrink_to_fit(wo, wob, wob_pinned, lambda w: fits(hob, w))
        if not fits(hob, wob):
            raise VmemMisfitError(
                f"pointwise wgrad tile does not fit VMEM at hob={hob}, "
                f"wob={wob}: needs more than {machine.vmem_bytes} bytes "
                f"resident")
    return Blocking(cob=cob, cib=cib, hob=hob, wob=wob)
