"""Measured conv dispatch: one decision point for every conv entry (§12).

The repo grew many ways to run the same convolution — the window Pallas
kernel, the streamed halo-DMA Pallas kernel, the depthwise / grouped /
pointwise specializations, im2col+GEMM, ``lax.conv`` and the blocked jnp
oracle — and until ISSUE 6 the choice between them was scattered boolean
plumbing threaded through kernels, layers, the train step and the serving
tier, with routing decided by *feasibility only* ("does the window
inequality fit VMEM").  ``BENCH_baseline.json`` shows why that is wrong:
im2col beats the window path on the smoke shapes while only the streamed
path survives the deep-pencil pathology — the right impl is a property of
the (geometry, dtype, machine, direction) point, and it should be
*measured*.

This module is the replacement: a first-class dispatch subsystem.

  ``DispatchKey``      frozen/hashable; wraps a :class:`ConvSpec` (the one
                       geometry object — extents, groups, dilation, pads)
                       plus precision name, machine name and direction
                       ∈ {fwd, dgrad, wgrad}.
  ``Impl``             the open-ended candidate enum (The Indirect
                       Convolution Algorithm argues for exactly this:
                       keep the set extensible, don't bake one kernel in).
  ``ConvDispatcher``   resolves key -> impl by precedence:
                         1. per-call override (tests, forced paths),
                         2. the persistent JSON dispatch table
                            (``repro/configs/dispatch_table.json``,
                            checked in; ``tune()`` writes winners back),
                         3. the analytical prior — blocking-model
                            feasibility (``choose_blocking`` and friends)
                            with ``resident_bytes`` as the cost annotation.
                       Every decision is observable: ``explain(key)``
                       returns the chosen impl, its source
                       (override/table/tuned/prior/fallback) and the losing
                       candidates' measured or predicted numbers.

The candidate set is geometry-dependent (``candidates_for``): dense convs
keep the ISSUE-6 set; depthwise geometry routes to the blocked depthwise
kernel, grouped geometry to the block-diagonal grouped window kernel, and
1x1/stride-1/unpadded geometry to the pointwise channel-matmul fast path —
each the *direct* form of its geometry (the paper's thesis), with the jnp
oracle and ``lax`` as the always-feasible references.

The ``VmemMisfitError`` fallback chain that used to live as try/except
around each kernel launch lives here now: feasibility is *probed* against
the same blocking model the kernel will use (same pencil pins, same
itemsize), so an infeasible candidate is never launched.

Persistence is schema 3 (``SCHEMA_VERSION``): entries carry ``groups``,
``dilation`` and the key's ``fusion`` tag (which epilogue/prologue riders —
residual / gap / in-kernel dz — the launch fuses; "" = unfused, and the
ident only grows a suffix when the tag is non-empty, so unfused idents are
schema-stable).  Older tables load through chained automatic migrations —
schema-1 entries (dense-only keys) gain ``groups=1`` / ``dilation=(1,1)``,
schema-2 entries gain ``fusion=""`` (every legacy entry is an unfused
conv) — with idents re-derived; any other schema raises with the schema
named (the CI gate's clear-failure contract).

Numerics contract: WINDOW, STREAM and JNP are interchangeable bit for bit
(the streamed/window bitwise property is test-pinned since ISSUE 5; the
oracle defines the semantics both kernels implement).  IM2COL and LAX agree
to float tolerance — their contraction order differs — so the prior never
selects them; they win only by measurement, and the equivalence sweep in
``tests/test_dispatch.py`` pins the agreement at the dispatch layer.
"""
from __future__ import annotations

import dataclasses
import enum
import json
import pathlib
import warnings
from typing import Callable, Dict, Iterable, Optional, Tuple, Union

from .backend import resolve_interpret, resolve_machine
from .blocking import (MachineModel, TPU_V5E, CPU_HASWELL, VmemMisfitError,
                       choose_blocking, choose_depthwise_blocking,
                       choose_depthwise_wgrad_blocking, choose_dgrad_blocking,
                       choose_pointwise_blocking,
                       choose_pointwise_dgrad_blocking,
                       choose_pointwise_wgrad_blocking,
                       choose_stream_blocking, choose_stream_dgrad_blocking,
                       choose_stream_wgrad_blocking, choose_wgrad_blocking,
                       depthwise_resident_bytes,
                       depthwise_wgrad_resident_bytes,
                       pointwise_dgrad_resident_bytes,
                       pointwise_resident_bytes,
                       pointwise_wgrad_resident_bytes,
                       resident_bytes, stream_resident_bytes,
                       stream_wgrad_resident_bytes, wgrad_resident_bytes)
from .conv_baselines import Padding
from .convspec import ConvSpec, as_dilation
from .errors import DispatchTableError
from .layout import choose_pencil
from .precision import resolve_precision
from repro.utils.faults import inject as _inject_fault

__all__ = [
    "Impl", "Direction", "DispatchKey", "KernelRoute", "Decision",
    "ConvDispatcher", "get_dispatcher", "set_dispatcher",
    "register_machine", "get_machine", "default_table_path",
    "stream_flag", "route_pallas", "run_conv_impl", "candidates_for",
    "FUSION_TOKENS",
]

Direction = str          # "fwd" | "dgrad" | "wgrad"
DIRECTIONS: Tuple[Direction, ...] = ("fwd", "dgrad", "wgrad")

SCHEMA_VERSION = 3

# canonical order of the fusion-tag tokens (DispatchKey.fusion): "res" and
# "gap" name forward epilogue riders, "dz" the backward in-kernel cotangent
# prologue (which carries the fused db on wgrad).
FUSION_TOKENS = ("res", "gap", "dz")


class Impl(enum.Enum):
    """The conv implementation candidates.  Open-ended by design — adding a
    member (plus its runner/probe) is the whole cost of a new candidate."""

    WINDOW = "window"        # window Pallas kernel (BlockSpec halo windows)
    STREAM = "stream"        # streamed halo-DMA Pallas kernel (HBM ring)
    DEPTHWISE = "depthwise"  # blocked depthwise Pallas kernel (per-lane taps)
    GROUPED = "grouped"      # window kernel w/ block-diagonal weight tiles
    POINTWISE = "pointwise"  # 1x1-as-matmul Pallas kernel (no halo machinery)
    IM2COL = "im2col"        # pack + GEMM baseline (memory-overhead-ful)
    LAX = "lax"              # XLA's own conv (lax.conv_general_dilated)
    JNP = "jnp"              # blocked jnp oracle (XLA-scheduled direct form)

    def __str__(self) -> str:            # JSON-friendly
        return self.value


def _as_impl(impl: Union["Impl", str, None]) -> Optional["Impl"]:
    if impl is None or isinstance(impl, Impl):
        return impl
    try:
        return Impl(impl)
    except ValueError:
        raise ValueError(
            f"unknown conv impl {impl!r}; have "
            f"{[m.value for m in Impl]}") from None


# The dense Pallas kernel family: bitwise-interchangeable tiled variants the
# kernel-level router picks between (dgrad/wgrad can only route here — the
# custom VJP's backward *is* these kernels).
PALLAS_IMPLS = (Impl.WINDOW, Impl.STREAM)

# The geometry specializations: each is the direct blocked form of its
# geometry, with its own custom-VJP kernel family.
SPECIALIZED_IMPLS = (Impl.DEPTHWISE, Impl.GROUPED, Impl.POINTWISE)

# Everything that launches a Pallas kernel (and therefore answers to a VMEM
# blocking model in probe_impl).
PALLAS_FAMILY = PALLAS_IMPLS + SPECIALIZED_IMPLS

# Bitwise-equivalent impls: routing between these can never change numerics
# (test-pinned).  IM2COL/LAX agree to float tolerance only.
EXACT_IMPLS = (Impl.WINDOW, Impl.STREAM, Impl.JNP)

# Candidates per direction for *dense* geometry (groups=1, dilation=1, not
# pointwise) — the ISSUE-6 set, unchanged.  Backward directions keep to the
# exact set: the custom VJP cannot splice a packing baseline into one leg of
# its backward, and the oracle's vjp is the reference the kernels are diffed
# against.  Non-dense geometry resolves through candidates_for().
CANDIDATES: Dict[Direction, Tuple[Impl, ...]] = {
    "fwd": (Impl.WINDOW, Impl.STREAM, Impl.IM2COL, Impl.LAX, Impl.JNP),
    "dgrad": (Impl.WINDOW, Impl.STREAM, Impl.JNP),
    "wgrad": (Impl.WINDOW, Impl.STREAM, Impl.JNP),
}


def candidates_for(key: "DispatchKey") -> Tuple[Impl, ...]:
    """The geometry-aware candidate set for one key.

    Dense non-pointwise geometry keeps the ISSUE-6 ``CANDIDATES`` table
    verbatim.  Otherwise the geometry's specialized impl leads, followed by
    the always-feasible references (``lax`` handles every geometry XLA
    does; the jnp oracle handles everything; im2col and the streamed
    kernels are dense-only, so neither appears off the dense path).  Dense
    *dilated* convs stay with the window kernel — its taps are
    dilation-strided — minus the stream/im2col members that are not.
    """
    spec = key.spec
    dense = spec.groups == 1 and spec.dilation == (1, 1)
    if spec.is_pointwise:
        return (Impl.POINTWISE,) + CANDIDATES[key.direction]
    if dense:
        return CANDIDATES[key.direction]
    if spec.is_depthwise:
        special: Tuple[Impl, ...] = (Impl.DEPTHWISE,)
    elif spec.groups > 1:
        special = (Impl.GROUPED,)
    else:                                   # dense geometry, dilated taps
        special = (Impl.WINDOW,)
    refs = (Impl.LAX, Impl.JNP) if key.direction == "fwd" else (Impl.JNP,)
    return special + refs


# ---------------------------------------------------------------------------
# machine registry — DispatchKey stores the *name* (hashable, JSON-able);
# probes need the object back
# ---------------------------------------------------------------------------

_MACHINES: Dict[str, MachineModel] = {
    TPU_V5E.name: TPU_V5E,
    CPU_HASWELL.name: CPU_HASWELL,
}


def register_machine(machine: MachineModel) -> MachineModel:
    """Make a MachineModel resolvable by name (tuner CLIs, table reload)."""
    _MACHINES[machine.name] = machine
    return machine


def get_machine(name: str) -> MachineModel:
    try:
        return _MACHINES[name]
    except KeyError:
        raise KeyError(
            f"unknown machine {name!r}; registered: {sorted(_MACHINES)} "
            f"(register_machine() makes custom models resolvable)") from None


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchKey:
    """One routing decision's identity: the convolution's full geometry (a
    :class:`ConvSpec` — extents, groups, dilation, normalized pads), the
    precision policy's short name, the machine model's name and the pass
    direction.  Frozen + hashable (dict key, jit-static safe); ``ident``
    is the canonical string the persistent table is keyed by."""

    spec: ConvSpec
    dtype: str                      # precision policy short name (f32/bf16)
    machine: str                    # MachineModel.name
    direction: Direction            # fwd | dgrad | wgrad
    fusion: str = ""                # "+"-joined FUSION_TOKENS subset, "" =
                                    # unfused (ident-stable with schema 2)

    def __post_init__(self):
        if self.direction not in DIRECTIONS:
            raise ValueError(f"direction must be one of {DIRECTIONS}, "
                             f"got {self.direction!r}")
        toks = [t for t in self.fusion.split("+") if t] if self.fusion else []
        bad = [t for t in toks if t not in FUSION_TOKENS]
        if bad:
            raise ValueError(f"unknown fusion token(s) {bad}; have "
                             f"{list(FUSION_TOKENS)}")
        canon = "+".join(t for t in FUSION_TOKENS if t in toks)
        if canon != self.fusion:         # canonical order/dedup -> one ident
            object.__setattr__(self, "fusion", canon)

    @classmethod
    def make(cls, n: int, hi: int, wi: int, ci: int, co: int, hf: int,
             wf: int, stride: int = 1, padding: Padding = "VALID",
             precision=None, machine: Optional[MachineModel] = None,
             direction: Direction = "fwd", *, groups: int = 1,
             dilation=1, fusion: str = "") -> "DispatchKey":
        """Build a key from call-site vocabulary (padding normalized by
        ``ConvSpec.make``, so SAME/int/explicit pads all land on one
        canonical identity — SAME resolves against the *dilated* filter
        extent).  The machine model is registered as a side effect, so
        custom models (tests, pathological budgets) resolve by name in the
        probes.  ``machine`` defaults to the running device's model."""
        machine = register_machine(resolve_machine(machine))
        spec = ConvSpec.make(n, hi, wi, ci, co, hf, wf, stride=stride,
                             padding=padding, groups=groups,
                             dilation=dilation)
        return cls(spec=spec, dtype=resolve_precision(precision).name,
                   machine=machine.name, direction=direction, fusion=fusion)

    @classmethod
    def from_shape(cls, s, precision=None,
                   machine: Optional[MachineModel] = None,
                   direction: Direction = "fwd",
                   fusion: str = "") -> "DispatchKey":
        """From a ``memory_model.ConvShape`` (the benchmark vocabulary)."""
        return cls.make(s.n, s.hi, s.wi, s.ci, s.co, s.hf, s.wf, s.stride,
                        s.pad, precision, machine, direction,
                        groups=getattr(s, "groups", 1),
                        dilation=getattr(s, "dilation", 1), fusion=fusion)

    def with_direction(self, direction: Direction) -> "DispatchKey":
        return dataclasses.replace(self, direction=direction)

    def shard(self, data: int = 1, model: int = 1) -> "DispatchKey":
        """The key a single shard of a (data x model) mesh resolves: batch
        over ``data``, output channels over ``model`` (``ConvSpec.shard``).
        The serving tier tunes and benches *these* keys — the per-shard
        geometry is what the kernel actually runs."""
        return dataclasses.replace(self, spec=self.spec.shard(data, model))

    # --- geometry delegation (the probes' vocabulary is the spec's) ---

    @property
    def n(self) -> int:
        return self.spec.n

    @property
    def hi(self) -> int:
        return self.spec.hi

    @property
    def wi(self) -> int:
        return self.spec.wi

    @property
    def ci(self) -> int:
        return self.spec.ci

    @property
    def co(self) -> int:
        return self.spec.co

    @property
    def hf(self) -> int:
        return self.spec.hf

    @property
    def wf(self) -> int:
        return self.spec.wf

    @property
    def stride(self) -> int:
        return self.spec.stride

    @property
    def pads(self):
        return self.spec.pads

    @property
    def groups(self) -> int:
        return self.spec.groups

    @property
    def dilation(self) -> Tuple[int, int]:
        return self.spec.dilation

    @property
    def padded_hi(self) -> int:
        return self.spec.padded_hi

    @property
    def padded_wi(self) -> int:
        return self.spec.padded_wi

    @property
    def ho(self) -> int:
        return self.spec.ho

    @property
    def wo(self) -> int:
        return self.spec.wo

    def flops(self) -> int:
        return self.spec.flops()

    @property
    def ident(self) -> str:
        """Canonical table key, stable across processes."""
        s = self.spec
        (ph0, ph1), (pw0, pw1) = s.pads
        dh, dw = s.dilation
        base = (f"{self.direction}|n{s.n}hi{s.hi}wi{s.wi}"
                f"ci{s.ci}co{s.co}f{s.hf}x{s.wf}s{s.stride}"
                f"p{ph0}.{ph1}.{pw0}.{pw1}g{s.groups}d{dh}.{dw}"
                f"|{self.dtype}|{self.machine}")
        # suffix only when fused: unfused idents stay schema-2-stable
        return f"{base}|{self.fusion}" if self.fusion else base

    def to_json(self) -> dict:
        s = self.spec
        return {
            "n": s.n, "hi": s.hi, "wi": s.wi, "ci": s.ci,
            "co": s.co, "hf": s.hf, "wf": s.wf,
            "stride": s.stride,
            "pads": [list(side) for side in s.pads],
            "groups": s.groups, "dilation": list(s.dilation),
            "dtype": self.dtype, "machine": self.machine,
            "direction": self.direction,
            **({"fusion": self.fusion} if self.fusion else {}),
        }

    @classmethod
    def from_json(cls, d: dict) -> "DispatchKey":
        """Schema-3 entries carry fusion; schema-2 entries carry
        groups/dilation; schema-1 entries (dense unfused convs by
        construction) default everything — this is the migration."""
        spec = ConvSpec(
            n=d["n"], hi=d["hi"], wi=d["wi"], ci=d["ci"], co=d["co"],
            hf=d["hf"], wf=d["wf"], stride=d["stride"],
            pads=tuple(tuple(side) for side in d["pads"]),
            groups=d.get("groups", 1),
            dilation=as_dilation(tuple(d.get("dilation", (1, 1)))))
        return cls(spec=spec, dtype=d["dtype"], machine=d["machine"],
                   direction=d["direction"], fusion=d.get("fusion", ""))


# ---------------------------------------------------------------------------
# the resolved kernel route — what the Pallas wrapper family consumes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelRoute:
    """Per-direction window/stream resolution for one Pallas conv launch.

    Rides in the wrappers' ``stream`` slot (frozen/hashable — jit-static and
    custom-vjp-nondiff safe), so the dispatcher can route forward, dgrad and
    wgrad *independently* (a key is per-direction) while the legacy
    ``stream=True/False/None`` bool keeps meaning "force all three" /
    "probe each".  Each field: True = streamed, False = window, None = probe
    feasibility at launch (the analytical prior)."""

    fwd: Optional[bool] = None
    dgrad: Optional[bool] = None
    wgrad: Optional[bool] = None

    def get(self, direction: Direction) -> Optional[bool]:
        return getattr(self, direction)


def stream_flag(stream, direction: Direction):
    """Extract one direction's stream knob from bool/None/KernelRoute —
    the single reader every kernel wrapper uses."""
    if isinstance(stream, KernelRoute):
        return stream.get(direction)
    return stream


def policy_name_for(dtype) -> str:
    """Map an operand dtype to its precision-policy short name (the
    DispatchKey dtype vocabulary)."""
    import numpy as np
    return "bf16" if np.dtype(dtype).itemsize == 2 else "f32"


def route_pallas(direction: Direction, *, n: int, hi: int, wi: int, ci: int,
                 co: int, hf: int, wf: int, stride: int,
                 machine: MachineModel, dtype, cob: int, cib: int,
                 hob: Optional[int] = None,
                 wob: Optional[int] = None) -> bool:
    """Kernel-level window/stream resolution for one *dense* launch:
    ``True`` = streamed.  This is the relocated ``VmemMisfitError`` fallback
    chain — instead of launching the window kernel and catching its
    blocking-model raise, the wrapper asks the same model *first* (same
    pencil pins, same itemsizes) and launches only the variant that fits; a
    shape misfitting both models raises here with the full chain named.
    ``hi``/``wi`` are the *padded* input extents (wrappers operate
    post-padding, VALID); for dgrad/wgrad pass the touched extents
    ``(out-1)*stride + filter`` so the derived ``ho``/``wo`` match the
    cotangent.  Pure function of static shapes/machine/dtype — safe at jit
    trace time.  Non-dense launches never call this: the streamed kernels
    are dense-only, so the wrappers pin the window family directly."""
    key = DispatchKey(spec=ConvSpec(n=n, hi=hi, wi=wi, ci=ci, co=co,
                                    hf=hf, wf=wf, stride=stride),
                      dtype=policy_name_for(dtype), machine=machine.name,
                      direction=direction)
    if probe_impl(key, Impl.WINDOW, cob, cib, hob, wob,
                  machine=machine)["feasible"]:
        return False
    probe = probe_impl(key, Impl.STREAM, cob, cib, hob, wob, machine=machine)
    if probe["feasible"]:
        return True
    raise VmemMisfitError(
        f"{direction} conv misfits both Pallas variants on "
        f"{machine.name}: the window inequality fails even at "
        f"hob = wob = 1 and the streamed floor fails too "
        f"({probe.get('error')})")


# ---------------------------------------------------------------------------
# feasibility probes + cost prior — the analytical blocking model, asked
# *before* launch (this is where the VmemMisfitError fallback now lives)
# ---------------------------------------------------------------------------

def _probe(chooser: Callable, bytes_fn: Callable, **kw) -> dict:
    """Run one blocking model; -> {feasible, resident_bytes | error}."""
    try:
        blk = chooser(**kw)
    except VmemMisfitError as e:
        return {"feasible": False, "error": str(e).split(".")[0]}
    except ValueError:
        raise                      # invalid arguments must always propagate
    return {"feasible": True, "resident_bytes": bytes_fn(blk, kw)}


def _geometry_gate(key: "DispatchKey", impl: Impl) -> Optional[str]:
    """Why ``impl`` cannot serve ``key``'s geometry at all (None = it can).

    This is the structural layer of the probe: the VMEM inequality only
    gets asked for (impl, geometry) pairs the kernel actually implements.
    """
    spec = key.spec
    dense = spec.groups == 1 and spec.dilation == (1, 1)
    if impl is Impl.STREAM and not dense:
        return ("streamed halo-DMA kernels are dense-only "
                "(groups=1, dilation=1)")
    if impl is Impl.IM2COL and not dense:
        return "im2col baseline is dense-only (groups=1, dilation=1)"
    if impl is Impl.WINDOW and spec.groups > 1:
        return "grouped geometry routes through the grouped impl"
    if impl is Impl.GROUPED and (spec.groups == 1 or spec.is_depthwise):
        return ("grouped impl serves 1 < groups < C geometry (dense has "
                "window, depthwise its own kernel)")
    if impl is Impl.DEPTHWISE and not spec.is_depthwise:
        return "depthwise kernel needs groups == ci == co"
    if impl is Impl.POINTWISE and not spec.is_pointwise:
        return "pointwise fast path needs 1x1/stride-1/unpadded dense geometry"
    return None


def _default_pencils(key: "DispatchKey",
                     machine: MachineModel) -> Tuple[int, int]:
    """(cob, cib) the blocked layout would choose for this geometry —
    per-group for grouped convs, full-lane for depthwise maps."""
    spec = key.spec
    if spec.is_depthwise:
        cb = choose_pencil(key.ci, machine.n_vec)
        return cb, cb
    return (choose_pencil(key.co, machine.n_vec, groups=spec.groups),
            choose_pencil(key.ci, machine.n_vec, groups=spec.groups))


def probe_impl(key: DispatchKey, impl: Impl,
               cob: Optional[int] = None, cib: Optional[int] = None,
               hob: Optional[int] = None, wob: Optional[int] = None,
               machine: Optional[MachineModel] = None) -> dict:
    """Feasibility + cost prior for one candidate at one key.

    Pallas-family impls ask the same blocking model (same pencil pins, same
    policy itemsize) the kernel wrapper will ask at launch, so "feasible
    here" means "will not raise there" — after a structural gate rejecting
    (impl, geometry) pairs the kernel does not implement (e.g. streamed
    kernels on grouped geometry).  The reference impls are always feasible
    (no VMEM inequality) and carry no resident-bytes prior.  ``cob``/``cib``
    default to the pencils the blocked layout would choose — pass the
    operands' real pencils when you have them.  ``machine`` overrides the
    registry lookup (kernel wrappers hold the model object; the key only
    names it).
    """
    if machine is None:
        machine = get_machine(key.machine)
    why_not = _geometry_gate(key, impl)
    if why_not is not None:
        return {"feasible": False, "error": why_not}
    if impl not in PALLAS_FAMILY:
        return {"feasible": True}
    if cob is None or cib is None:
        dcob, dcib = _default_pencils(key, machine)
        cob = dcob if cob is None else cob
        cib = dcib if cib is None else cib
    pol = resolve_precision(key.dtype)
    spec = key.spec
    dil = spec.dilation
    common = dict(machine=machine, precision=pol)
    # the fusion tag's per-direction reading: forward launches see the
    # epilogue riders, backward launches the in-kernel cotangent prologue
    # (wgrad's fused db always rides with dz — one flush, one flag)
    toks = set(key.fusion.split("+")) if key.fusion else set()
    f_res, f_gap = "res" in toks, "gap" in toks
    f_dz = "dz" in toks

    if impl is Impl.DEPTHWISE:
        if key.direction == "fwd":
            return _probe(
                choose_depthwise_blocking,
                lambda b, kw: depthwise_resident_bytes(
                    b.hob, b.wob, b.cob, key.hf, key.wf, key.stride,
                    pol.operand_itemsize, pol.accum_itemsize, dil,
                    fused_residual=f_res, fused_gap=f_gap),
                hi=key.padded_hi, wi=key.padded_wi, c=key.ci,
                hf=key.hf, wf=key.wf, stride=key.stride, cb=cib,
                hob=hob, wob=wob, dilation=dil,
                fused_residual=f_res, fused_gap=f_gap, **common)
        if key.direction == "dgrad":
            # the dgrad IS the forward kernel at stride 1 (taps still
            # dilated) over the stride-dilated cotangent, padded to the
            # unpadded input plus the filter reach
            eh = key.hi + (key.hf - 1) * dil[0]
            ew = key.wi + (key.wf - 1) * dil[1]
            return _probe(
                choose_depthwise_blocking,
                lambda b, kw: depthwise_resident_bytes(
                    b.hob, b.wob, b.cob, key.hf, key.wf, 1,
                    pol.operand_itemsize, pol.accum_itemsize, dil,
                    fused_prologue=f_dz),
                hi=eh, wi=ew, c=key.ci, hf=key.hf, wf=key.wf, stride=1,
                cb=cib, hob=hob, wob=wob, dilation=dil,
                fused_prologue=f_dz, **common)
        return _probe(
            choose_depthwise_wgrad_blocking,
            lambda b, kw: depthwise_wgrad_resident_bytes(
                b.hob, b.wob, b.cob, key.hf, key.wf, key.stride,
                pol.operand_itemsize, pol.accum_itemsize, dil,
                fused_prologue=f_dz, fused_bias=f_dz),
            ho=key.ho, wo=key.wo, hf=key.hf, wf=key.wf, stride=key.stride,
            cb=cib, hob=hob, wob=wob, dilation=dil,
            fused_prologue=f_dz, fused_bias=f_dz, **common)

    if impl is Impl.POINTWISE:
        if key.direction == "fwd":
            return _probe(
                choose_pointwise_blocking,
                lambda b, kw: pointwise_resident_bytes(
                    b.hob, b.wob, b.cob, b.cib,
                    pol.operand_itemsize, pol.accum_itemsize,
                    fused_residual=f_res, fused_gap=f_gap),
                hi=key.padded_hi, wi=key.padded_wi, ci=key.ci, co=key.co,
                cob=cob, cib=cib, hob=hob, wob=wob,
                fused_residual=f_res, fused_gap=f_gap, **common)
        if key.direction == "dgrad" and f_dz:
            # every input-channel block in one step, dz formed once
            ciblk = -(-key.ci // cib)
            return _probe(
                choose_pointwise_dgrad_blocking,
                lambda b, kw: pointwise_dgrad_resident_bytes(
                    b.hob, b.wob, b.cob, b.cib, ciblk,
                    pol.operand_itemsize, pol.accum_itemsize),
                ho=key.ho, wo=key.wo, ciblk=ciblk, cob=cob, cib=cib,
                hob=hob, wob=wob, **common)
        if key.direction == "dgrad":
            # transposed channel matmul: pencils swap roles
            return _probe(
                choose_pointwise_blocking,
                lambda b, kw: pointwise_resident_bytes(
                    b.hob, b.wob, b.cob, b.cib,
                    pol.operand_itemsize, pol.accum_itemsize),
                hi=key.ho, wi=key.wo, ci=key.co, co=key.ci,
                cob=cib, cib=cob, hob=hob, wob=wob, **common)
        # the wgrad reads the dgrad's dz: no prologue, db rides with it
        return _probe(
            choose_pointwise_wgrad_blocking,
            lambda b, kw: pointwise_wgrad_resident_bytes(
                b.hob, b.wob, b.cob, b.cib,
                pol.operand_itemsize, pol.accum_itemsize,
                fused_bias=f_dz),
            ho=key.ho, wo=key.wo, cob=cob, cib=cib, hob=hob, wob=wob,
            fused_bias=f_dz, **common)

    groups = spec.groups                 # WINDOW (dense) / GROUPED / STREAM
    if key.direction == "fwd":
        args = dict(hi=key.padded_hi, wi=key.padded_wi, ci=key.ci, co=key.co,
                    hf=key.hf, wf=key.wf, stride=key.stride,
                    cob=cob, cib=cib, hob=hob, wob=wob, **common)
        if impl in (Impl.WINDOW, Impl.GROUPED):
            return _probe(
                choose_blocking,
                lambda b, kw: resident_bytes(
                    b.hob, b.wob, b.cob, b.cib, key.hf, key.wf, key.stride,
                    pol.operand_itemsize, pol.accum_itemsize, dil,
                    fused_residual=f_res, fused_gap=f_gap),
                groups=groups, dilation=dil,
                fused_residual=f_res, fused_gap=f_gap, **args)
        return _probe(
            choose_stream_blocking,
            lambda b, kw: stream_resident_bytes(
                b.hso, b.hob, b.wob, b.cob, b.cib, key.hf, key.wf,
                key.stride, pol.operand_itemsize, pol.accum_itemsize,
                fused_residual=f_res, fused_gap=f_gap),
            fused_residual=f_res, fused_gap=f_gap, **args)

    if key.direction == "dgrad":
        args = dict(ho=key.ho, wo=key.wo, ci=key.ci, co=key.co,
                    hf=key.hf, wf=key.wf, stride=key.stride,
                    cib=cib, cob=cob, hob=hob, wob=wob, **common)
        if impl in (Impl.WINDOW, Impl.GROUPED):
            return _probe(
                choose_dgrad_blocking,
                lambda b, kw: resident_bytes(
                    b.hob, b.wob, b.cob, b.cib, key.hf, key.wf, 1,
                    pol.operand_itemsize, pol.accum_itemsize, dil,
                    fused_prologue=f_dz),
                groups=groups, dilation=dil, fused_prologue=f_dz, **args)
        # streamed backward stays unfused: the wrappers apply the cotangent
        # prologue outside the ring, so the model is unchanged under dz
        return _probe(
            choose_stream_dgrad_blocking,
            lambda b, kw: stream_resident_bytes(
                b.hso, b.hob, b.wob, b.cob, b.cib, key.hf, key.wf, 1,
                pol.operand_itemsize, pol.accum_itemsize), **args)

    # wgrad: channel pencils are pinned by the operand layouts
    args = dict(ho=key.ho, wo=key.wo, hf=key.hf, wf=key.wf,
                stride=key.stride, cob=cob, cib=cib, **common)
    if impl in (Impl.WINDOW, Impl.GROUPED):
        return _probe(
            choose_wgrad_blocking,
            lambda b, kw: wgrad_resident_bytes(
                b.hob, b.wob, b.cob, b.cib, key.hf, key.wf, key.stride,
                pol.operand_itemsize, pol.accum_itemsize, dil,
                fused_prologue=f_dz, fused_bias=f_dz),
            hob=hob, wob=wob, dilation=dil,
            fused_prologue=f_dz, fused_bias=f_dz, **args)
    return _probe(
        choose_stream_wgrad_blocking,
        lambda b, kw: stream_wgrad_resident_bytes(
            b.hso, b.wob, b.cob, b.cib, key.hf, key.wf, key.stride,
            pol.operand_itemsize, pol.accum_itemsize),
        wob=wob, **args)


def _pallas_costly() -> bool:
    """True when a Pallas launch would run in interpret mode (non-TPU
    backend): the prior then prefers the XLA-scheduled oracle, preserving
    the pre-dispatcher default for untouched call sites."""
    return resolve_interpret(None)


def prior_order(key: DispatchKey,
                candidates: Tuple[Impl, ...]) -> Tuple[Impl, ...]:
    """The analytical prior's preference order over ``candidates``.

    The geometry's specialized impl first where one exists (depthwise /
    grouped / pointwise — each is the *direct* blocked form of its
    geometry, the paper's thesis applied to the kernel zoo; measurement can
    still demote it through the table tier).  Then direct dense impls:
    window before stream (the streamed ring pays manual-DMA orchestration
    the window path gets from the Pallas pipeliner); the jnp oracle leads
    the dense forward on non-TPU backends where a kernel launch would be
    interpret-mode.  IM2COL/LAX are never prior-chosen — they win only by
    measurement.
    """
    spec = key.spec
    if spec.is_pointwise:
        special: Tuple[Impl, ...] = (Impl.POINTWISE,)
    elif spec.is_depthwise:
        special = (Impl.DEPTHWISE,)
    elif spec.groups > 1:
        special = (Impl.GROUPED,)
    else:
        special = ()
    if key.direction == "fwd" and _pallas_costly():
        pref = special + (Impl.JNP, Impl.WINDOW, Impl.STREAM)
    else:
        pref = special + (Impl.WINDOW, Impl.STREAM, Impl.JNP)
    return tuple(i for i in pref if i in candidates) + tuple(
        i for i in candidates if i not in pref)


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Decision:
    """One resolved routing: the impl, where the choice came from
    (override | table | tuned | prior | prior-fallback | table-fallback),
    and the evidence (measured times for table/tuned, probe results for
    prior)."""

    impl: Impl
    source: str
    key: DispatchKey
    times_us: Optional[Dict[str, float]] = None
    probes: Optional[Dict[str, dict]] = None

    @property
    def stream(self) -> Optional[bool]:
        """The legacy kernel knob this decision implies (None = not a
        window/stream-family decision)."""
        if self.impl is Impl.STREAM:
            return True
        if self.impl is Impl.WINDOW:
            return False
        return None


def default_table_path() -> pathlib.Path:
    """The checked-in persistent dispatch table (repro/configs/)."""
    return (pathlib.Path(__file__).resolve().parent.parent
            / "configs" / "dispatch_table.json")


def _migrate_v1(entries: Dict[str, dict]) -> Dict[str, dict]:
    """Schema-1 -> schema-2 table migration.

    Every schema-1 entry is a dense conv by construction (the key had no
    groups/dilation fields), so ``DispatchKey.from_json``'s defaults fill
    in ``groups=1`` / ``dilation=(1,1)`` and the entry is re-keyed by the
    re-derived (schema-2) ident.  The measured evidence rides along
    untouched."""
    out: Dict[str, dict] = {}
    for entry in entries.values():
        key = DispatchKey.from_json(entry["key"])
        out[key.ident] = dict(entry, key=key.to_json())
    return out


def _migrate_v2(entries: Dict[str, dict]) -> Dict[str, dict]:
    """Schema-2 -> schema-3 table migration.

    Every schema-2 entry is an *unfused* conv by construction (the key had
    no fusion field), so ``from_json`` defaults ``fusion=""`` — and since
    unfused idents carry no fusion suffix, the re-derived idents are
    byte-identical to the schema-2 ones.  The measured evidence rides along
    untouched."""
    out: Dict[str, dict] = {}
    for entry in entries.values():
        key = DispatchKey.from_json(entry["key"])
        out[key.ident] = dict(entry, key=key.to_json())
    return out


class ConvDispatcher:
    """key -> impl, by override > table > analytical prior.

    The table is a plain dict ``ident -> entry`` mirroring the JSON schema;
    ``tune()`` measures the feasible candidates and writes the winner back
    (in memory — ``save()`` persists).  Instances hash by identity, so they
    ride through ``lru_cache``'d serving wrappers; the module-level default
    (``get_dispatcher()``) lazy-loads the checked-in table.
    """

    def __init__(self, table: Optional[dict] = None,
                 path: Optional[pathlib.Path] = None):
        self.table: Dict[str, dict] = dict(table or {})
        self.path = pathlib.Path(path) if path is not None else None
        self._tuned: set = set()         # idents measured in this process

    # --- persistence ---

    @classmethod
    def from_file(cls, path=None, missing_ok: bool = True
                  ) -> "ConvDispatcher":
        path = pathlib.Path(path) if path is not None else default_table_path()
        if not path.exists():
            if missing_ok:
                return cls(path=path)
            raise FileNotFoundError(path)
        # Corruption is transient (DESIGN.md §16): a truncated/garbled file
        # costs the measured evidence, not correctness — the analytical
        # prior still routes every shape.  One warning, then degrade.  An
        # *unknown schema* is a different animal: the file is intact and
        # from the future; silently dropping it would hide real data, so
        # that still fails loudly by name (pinned in tests/test_dispatch).
        def _degrade(exc: Exception) -> "ConvDispatcher":
            warnings.warn(
                f"{DispatchTableError.__name__} (transient): dispatch table "
                f"{path} could not be loaded ({exc}); routing degrades to "
                "the analytical prior — regenerate with "
                "`python -m benchmarks.tune_dispatch`",
                RuntimeWarning, stacklevel=3)
            return cls(path=path)

        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                raise DispatchTableError(f"top level is {type(doc).__name__}"
                                         ", expected an object")
        except (OSError, UnicodeDecodeError, json.JSONDecodeError,
                DispatchTableError) as exc:
            return _degrade(exc)
        schema = doc.get("schema")
        entries = doc.get("entries", {})
        try:
            if not isinstance(entries, dict):
                raise DispatchTableError(
                    f"entries is {type(entries).__name__}, expected a map")
            if schema == 1:
                entries = _migrate_v2(_migrate_v1(entries))  # dense legacy
            elif schema == 2:
                entries = _migrate_v2(entries)  # unfused-only legacy table
            elif schema != SCHEMA_VERSION:
                raise ValueError(
                    f"dispatch table {path} has schema {schema!r}, expected "
                    f"{SCHEMA_VERSION} (or 1/2, which auto-migrate); "
                    "regenerate it with `python -m benchmarks.tune_dispatch`")
        except (KeyError, TypeError, AttributeError,
                DispatchTableError) as exc:    # malformed entries mid-migrate
            return _degrade(exc)
        return cls(table=entries, path=path)

    def to_json(self) -> dict:
        return {"schema": SCHEMA_VERSION,
                "entries": {k: self.table[k] for k in sorted(self.table)}}

    def save(self, path=None) -> pathlib.Path:
        path = pathlib.Path(path) if path is not None else self.path
        if path is None:
            raise ValueError("no path: pass save(path=...) or construct the "
                             "dispatcher with one")
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        self.path = path
        return path

    # --- resolution ---

    def lookup(self, key: DispatchKey) -> Optional[dict]:
        return self.table.get(key.ident)

    def decide(self, key: DispatchKey, override=None,
               candidates: Optional[Tuple[Impl, ...]] = None,
               cob: Optional[int] = None, cib: Optional[int] = None,
               hob: Optional[int] = None,
               wob: Optional[int] = None) -> Decision:
        """Resolve one key.  Precedence: ``override`` (an ``Impl`` or its
        name — per-call forcing always wins, feasibility included: a forced
        misfit raises at launch, exactly the old pinned-path contract) >
        table entry (checked-in or tuned this process) > analytical prior.
        ``candidates`` defaults to the geometry-aware ``candidates_for``
        set.  A table winner outside ``candidates`` or infeasible under the
        *actual* pencil pins degrades to the best measured in-set candidate,
        then to the prior (source records the degradation).
        """
        _inject_fault("dispatch.resolve")
        candidates = candidates or candidates_for(key)
        override = _as_impl(override)
        if override is not None:
            return Decision(impl=override, source="override", key=key)

        entry = self.lookup(key)
        if entry is not None and entry.get("source") == "prior":
            # a prior-seeded entry records coverage, not evidence: the prior
            # is a function of the running backend (``prior_order``), so it
            # is re-derived here rather than replayed from the machine that
            # seeded the file
            entry = None
        if entry is not None:
            impl = Impl(entry["impl"])
            source = "tuned" if key.ident in self._tuned else "table"
            times = entry.get("times_us")
            if impl in candidates and self._usable(key, impl, cob, cib,
                                                   hob, wob):
                return Decision(impl=impl, source=source, key=key,
                                times_us=times)
            # degrade inside the measured set before giving up on the data
            if times:
                ranked = sorted(
                    (t, name) for name, t in times.items()
                    if Impl(name) in candidates
                    and self._usable(key, Impl(name), cob, cib, hob, wob))
                if ranked:
                    return Decision(impl=Impl(ranked[0][1]),
                                    source=f"{source}-fallback", key=key,
                                    times_us=times)

        probes = {i.value: probe_impl(key, i, cob, cib, hob, wob)
                  for i in candidates}
        for impl in prior_order(key, candidates):
            if probes[impl.value]["feasible"]:
                return Decision(impl=impl, source="prior", key=key,
                                probes=probes)
        raise VmemMisfitError(
            f"no feasible conv impl for {key.ident}: every candidate in "
            f"{[c.value for c in candidates]} misfits its blocking model")

    def _usable(self, key, impl, cob, cib, hob, wob) -> bool:
        return probe_impl(key, impl, cob, cib, hob, wob)["feasible"]

    def kernel_route(self, key: DispatchKey, stream=None, hso=None,
                     cob: Optional[int] = None, cib: Optional[int] = None,
                     hob: Optional[int] = None,
                     wob: Optional[int] = None) -> KernelRoute:
        """Resolve all three directions of one window/stream-family Pallas
        launch to a frozen :class:`KernelRoute` (window/stream per
        direction).

        ``stream``/``hso`` are the legacy knobs: an explicit bool (or a
        strip height, which implies streaming) forces all three directions
        — the old contract — and a ``KernelRoute`` passes through.  With
        ``stream=None`` each direction resolves independently through
        ``decide()`` over the Pallas candidates; non-dense geometry
        (grouped/dilated) pins the window family outright, since the
        streamed kernels are dense-only.  ``hob``/``wob`` are the *forward*
        tile pins: backward tile sizes are per-kernel model choices over
        their own (dgrad-extent / cotangent) geometry, so the pins never
        reach the dgrad/wgrad probes — mirroring ``_conv_bwd``, which
        launches both backward kernels unpinned."""
        if isinstance(stream, KernelRoute):
            return stream
        if hso is not None:
            stream = True
        if stream is not None:
            return KernelRoute(fwd=stream, dgrad=stream, wgrad=stream)
        spec = key.spec
        if spec.groups > 1 or spec.dilation != (1, 1):
            return KernelRoute(fwd=False, dgrad=False, wgrad=False)
        flags = {}
        for d in DIRECTIONS:
            fwd = d == "fwd"
            dec = self.decide(key.with_direction(d),
                              candidates=PALLAS_IMPLS, cob=cob, cib=cib,
                              hob=hob if fwd else None,
                              wob=wob if fwd else None)
            flags[d] = dec.stream
        return KernelRoute(**flags)

    # --- observability ---

    def explain(self, key: DispatchKey, override=None,
                candidates: Optional[Tuple[Impl, ...]] = None) -> dict:
        """The decision plus every candidate's evidence: measured times
        where the table has them, feasibility + resident-bytes prior
        everywhere (the losing candidates' predicted or measured numbers,
        per the ISSUE contract)."""
        candidates = candidates or candidates_for(key)
        dec = self.decide(key, override=override, candidates=candidates)
        entry = self.lookup(key) or {}
        times = entry.get("times_us") or {}
        cands = {}
        for impl in candidates:
            info = dict(probe_impl(key, impl))
            if impl.value in times:
                info["measured_us"] = times[impl.value]
            cands[impl.value] = info
        return {"key": key.ident, "impl": dec.impl.value,
                "source": dec.source, "candidates": cands}

    # --- measurement ---

    def tune(self, key: DispatchKey, iters: int = 3,
             timer: Optional[Callable] = None, persist: bool = False,
             interpret: Optional[bool] = None) -> Decision:
        """Time every feasible candidate at ``key`` and record the winner.

        The timings use ``benchmarks.timing.time_fn`` (jit + warmup +
        median-of-k) on synthetic operands at the key's dtype; Pallas
        candidates run interpret-mode off-TPU, so off-TPU tables measure
        relative kernel trajectory, not TPU wall-clock (same contract as
        ``BENCH_*.json``).  The winning entry lands in the in-memory table
        (source "tuned"); ``persist=True`` saves the file too.
        """
        timer = timer or _default_timer()
        interpret = resolve_interpret(interpret)
        ops = _tune_operands(key)
        times: Dict[str, float] = {}
        for impl in candidates_for(key):
            if not probe_impl(key, impl)["feasible"]:
                continue
            fn, args = _tune_closure(key, impl, ops, interpret)
            times[impl.value] = float(timer(fn, *args, iters=iters) * 1e6)
        if not times:
            raise VmemMisfitError(
                f"no feasible candidate to tune at {key.ident}")
        winner = min(times, key=times.get)
        self.table[key.ident] = {
            "key": key.to_json(),
            "impl": winner,
            "source": "tuned",
            "times_us": {k: round(v, 3) for k, v in times.items()},
        }
        self._tuned.add(key.ident)
        if persist:
            self.save()
        return Decision(impl=Impl(winner), source="tuned", key=key,
                        times_us=self.table[key.ident]["times_us"])

    def seed_prior(self, key: DispatchKey) -> Decision:
        """Record the analytical prior's choice as a table entry (source
        "prior") — coverage without measurement, for shapes too large to
        time in CI; ``check_regression`` reports them as "untuned"."""
        dec = self.decide(key)
        self.table[key.ident] = {
            "key": key.to_json(),
            "impl": dec.impl.value,
            "source": "prior",
            "probes": dec.probes or {i.value: probe_impl(key, i)
                                     for i in candidates_for(key)},
        }
        return dec

    def coverage(self, keys: Iterable[DispatchKey]) -> dict:
        """Partition ``keys`` by table status: measured / prior-seeded /
        missing (the check_regression dispatch-coverage vocabulary)."""
        out = {"tuned": [], "prior": [], "missing": []}
        for key in keys:
            entry = self.lookup(key)
            if entry is None:
                out["missing"].append(key.ident)
            elif entry.get("source") == "prior":
                out["prior"].append(key.ident)
            else:
                out["tuned"].append(key.ident)
        return out


# ---------------------------------------------------------------------------
# impl runners — the one place each candidate's calling convention lives
# ---------------------------------------------------------------------------

def _blocked_groups(xb, wb) -> int:
    """The group count baked into a blocked (x, w) operand pair: the maps
    carry Ci, the grouped-HWIO weight carries Cig — their ratio is static
    shape information, never separate plumbing."""
    ci = xb.shape[1] * xb.shape[4]
    cig = wb.shape[1] * wb.shape[4]
    if ci % cig:
        raise ValueError(
            f"blocked weight input extent {cig} does not divide the maps' "
            f"channel count {ci} — not a grouped-HWIO pair")
    return ci // cig


def run_conv_impl(impl: Impl, xb, wb, bias=None, *, stride: int = 1,
                  padding: Padding = "VALID", activation=None,
                  precision=None, machine: Optional[MachineModel] = None,
                  interpret: Optional[bool] = None,
                  hob: Optional[int] = None, wob: Optional[int] = None,
                  hso: Optional[int] = None, route=None, dilation=1,
                  residual=None, gap: bool = False):
    """Execute one candidate on blocked operands, blocked output.

    All impls share this signature — blocked ``[N, Ci/Cib, H, W, Cib]``
    in, blocked ``[N, Co/Cob, Ho, Wo, Cob]`` out, fused bias + activation
    semantics, ``precision`` policy honored (operands cast once, f32
    accumulation, operand-dtype output) — so the dispatcher can swap them
    without the call site noticing anything but time.  The group count is
    *derived* from the operand shapes (grouped-HWIO weights carry Cig);
    only ``dilation`` needs stating.  IM2COL/LAX pay a layout round-trip
    (they are NHWC algorithms); that cost is *theirs to lose* in tune(),
    not hidden.  ``route`` (a :class:`KernelRoute`) rides into the
    window/stream wrappers' ``stream`` slot for per-direction backward
    routing.

    ``residual``/``gap`` are the §14 epilogue riders, honored by *every*
    impl with one semantics — residual added post-activation in f32, gap
    returning flat f32-mean ``[N, Co]`` features: the Pallas families fuse
    them in-kernel, the jnp oracle folds them into its epilogue, and the
    NHWC baselines apply them on the blocked result after the layout
    sandwich (so routing stays a pure performance decision)."""
    import jax.numpy as jnp

    impl = _as_impl(impl)
    pol = resolve_precision(precision)
    groups = _blocked_groups(xb, wb)
    dilation = as_dilation(dilation)

    if impl in PALLAS_IMPLS or impl is Impl.GROUPED:
        from repro.kernels.direct_conv2d import direct_conv2d_blocked_pallas
        if impl is Impl.GROUPED:
            stream = route if route is not None else False
        else:
            stream = route if route is not None else (impl is Impl.STREAM)
        return direct_conv2d_blocked_pallas(
            xb, wb, bias, stride=stride, padding=padding,
            activation=activation, hob=hob, wob=wob, machine=machine,
            interpret=interpret, precision=pol, stream=stream, hso=hso,
            groups=groups, dilation=dilation, residual=residual, gap=gap)
    if impl is Impl.DEPTHWISE:
        from repro.kernels.conv2d_depthwise import (
            depthwise_conv2d_blocked_pallas)
        return depthwise_conv2d_blocked_pallas(
            xb, wb, bias, stride=stride, padding=padding,
            activation=activation, hob=hob, wob=wob, machine=machine,
            interpret=interpret, precision=pol, dilation=dilation,
            residual=residual, gap=gap)
    if impl is Impl.POINTWISE:
        from repro.kernels.conv2d_pointwise import (
            pointwise_conv2d_blocked_pallas)
        return pointwise_conv2d_blocked_pallas(
            xb, wb, bias, stride=stride, padding=padding,
            activation=activation, hob=hob, wob=wob, machine=machine,
            interpret=interpret, precision=pol, residual=residual, gap=gap)
    if impl is Impl.JNP:
        from repro.core.direct_conv import direct_conv_blocked
        return direct_conv_blocked(xb, wb, stride, padding, bias,
                                   activation, hob=hob, wob=wob,
                                   precision=pol, groups=groups,
                                   dilation=dilation, residual=residual,
                                   gap=gap)
    if impl is Impl.IM2COL and (groups > 1 or dilation != (1, 1)):
        raise ValueError("im2col baseline is dense-only (groups=1, "
                         "dilation=1); the dispatcher's geometry gate "
                         "should have filtered it")

    # NHWC reference algorithms: layout sandwich + the same fused epilogue
    # semantics (bias added on the f32 result, activation, operand dtype out)
    from repro.core import layout as L
    from repro.core import conv_baselines as B
    from repro.core.direct_conv import apply_activation
    x = L.blocked_to_nhwc(xb).astype(pol.op_dtype)
    w = L.blocked_to_hwio(wb).astype(pol.op_dtype)
    if impl is Impl.IM2COL:
        y = B.conv_im2col(x, w, stride, padding).astype(jnp.float32)
    else:
        y = B.conv_lax(x, w, stride, padding, groups=groups,
                       dilation=dilation).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(-1).astype(jnp.float32)
    y = apply_activation(y, activation).astype(pol.op_dtype)
    yb = L.nhwc_to_blocked(y, xb_out_pencil(wb))
    if residual is not None:
        yb = (yb.astype(jnp.float32)
              + residual.astype(jnp.float32)).astype(pol.op_dtype)
    if gap:
        n, coblk, _, _, cob = yb.shape
        return jnp.mean(yb.astype(jnp.float32),
                        axis=(2, 3)).reshape(n, coblk * cob
                                             ).astype(pol.op_dtype)
    return yb


def xb_out_pencil(wb) -> int:
    """Output-channel pencil baked into a blocked weight tensor."""
    return wb.shape[-1]


# ---------------------------------------------------------------------------
# tune plumbing
# ---------------------------------------------------------------------------

def _default_timer() -> Callable:
    """``benchmarks.timing.time_fn`` when the benchmarks package is on the
    path (repo checkouts), else a minimal local equivalent (installed
    trees)."""
    try:
        from benchmarks.timing import time_fn
        return time_fn
    except ImportError:
        return _local_time_fn


def _local_time_fn(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    import time as _time
    import jax
    import numpy as np
    jfn = jax.jit(fn)
    for _ in range(warmup):
        jax.block_until_ready(jfn(*args))
    ts = []
    for _ in range(iters):
        t0 = _time.perf_counter()
        jax.block_until_ready(jfn(*args))
        ts.append(_time.perf_counter() - t0)
    return float(np.median(ts))


def _tune_operands(key: DispatchKey) -> dict:
    """Synthetic blocked operands (+ cotangent) at the key's dtype, in the
    geometry's layout (grouped-HWIO weights, per-group pencils; depthwise
    weights at Cig=1 with full-lane maps)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import layout as L

    machine = get_machine(key.machine)
    pol = resolve_precision(key.dtype)
    spec = key.spec
    lay = L.BlockedConvLayout.choose(key.ci, key.co, machine.n_vec,
                                     groups=spec.groups)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(key.n, key.hi, key.wi, key.ci)),
                    pol.op_dtype)
    w = jnp.asarray(rng.normal(size=(key.hf, key.wf, spec.cig, key.co)),
                    pol.op_dtype)
    xb = L.nhwc_to_blocked(x, lay.cb_in)
    wb = L.hwio_to_blocked(w, lay.cb_weight, lay.cb_out)
    dy = jnp.asarray(rng.normal(
        size=(key.n, key.co // lay.cb_out, key.ho, key.wo, lay.cb_out)),
        pol.op_dtype)
    from repro.core.direct_conv import pad_blocked
    xp = pad_blocked(xb, *key.pads)
    return {"xb": xb, "wb": wb, "dy": dy, "xp": xp,
            "cib": lay.cb_in, "cob": lay.cb_out, "machine": machine,
            "pol": pol}


def _tune_closure(key: DispatchKey, impl: Impl, ops: dict,
                  interpret: bool):
    """(callable, args) pair ``tune()`` hands to the timer for one
    candidate at one direction."""
    import jax
    machine, pol = ops["machine"], ops["pol"]
    groups, dilation = key.groups, key.dilation

    if key.direction == "fwd":
        def fwd(xb_, wb_):
            return run_conv_impl(impl, xb_, wb_, stride=key.stride,
                                 padding=key.pads, precision=pol,
                                 machine=machine, interpret=interpret,
                                 dilation=dilation)
        return fwd, (ops["xb"], ops["wb"])

    if key.direction == "dgrad":
        if impl in PALLAS_IMPLS or impl is Impl.GROUPED:
            from repro.kernels.direct_conv2d import direct_conv2d_dgrad_pallas

            def dgrad(dy_, wb_):
                return direct_conv2d_dgrad_pallas(
                    dy_, wb_, stride=key.stride, machine=machine,
                    interpret=interpret, stream=(impl is Impl.STREAM),
                    groups=groups, dilation=dilation)
            return dgrad, (ops["dy"], ops["wb"])
        if impl is Impl.DEPTHWISE:
            from repro.kernels.conv2d_depthwise import depthwise_dgrad_pallas

            def dgrad_dw(dy_, wb_):
                return depthwise_dgrad_pallas(
                    dy_, wb_, (key.hi, key.wi), stride=key.stride,
                    pads=key.pads, machine=machine, interpret=interpret,
                    dilation=dilation)
            return dgrad_dw, (ops["dy"], ops["wb"])
        if impl is Impl.POINTWISE:
            from repro.kernels.conv2d_pointwise import pointwise_dgrad_pallas

            def dgrad_pw(dy_, wb_):
                return pointwise_dgrad_pallas(
                    dy_, wb_, machine=machine, interpret=interpret)
            return dgrad_pw, (ops["dy"], ops["wb"])

        from repro.core.direct_conv import direct_conv_blocked

        def dgrad_jnp(dy_, xp_, wb_):
            _, vjp = jax.vjp(
                lambda x: direct_conv_blocked(x, wb_, key.stride, "VALID",
                                              precision=pol, groups=groups,
                                              dilation=dilation), xp_)
            return vjp(dy_)[0]
        return dgrad_jnp, (ops["dy"], ops["xp"], ops["wb"])

    # wgrad
    if impl in PALLAS_IMPLS or impl is Impl.GROUPED:
        from repro.kernels.direct_conv2d import direct_conv2d_wgrad_pallas

        def wgrad(xp_, dy_):
            return direct_conv2d_wgrad_pallas(
                xp_, dy_, key.hf, key.wf, stride=key.stride,
                machine=machine, interpret=interpret,
                stream=(impl is Impl.STREAM), groups=groups,
                dilation=dilation)
        return wgrad, (ops["xp"], ops["dy"])
    if impl is Impl.DEPTHWISE:
        from repro.kernels.conv2d_depthwise import depthwise_wgrad_pallas

        def wgrad_dw(xp_, dy_):
            return depthwise_wgrad_pallas(
                xp_, dy_, key.hf, key.wf, stride=key.stride,
                machine=machine, interpret=interpret, dilation=dilation)
        return wgrad_dw, (ops["xp"], ops["dy"])
    if impl is Impl.POINTWISE:
        from repro.kernels.conv2d_pointwise import pointwise_wgrad_pallas

        def wgrad_pw(xp_, dy_):
            return pointwise_wgrad_pallas(
                xp_, dy_, machine=machine, interpret=interpret)
        return wgrad_pw, (ops["xp"], ops["dy"])

    from repro.core.direct_conv import direct_conv_blocked

    def wgrad_jnp(dy_, xp_, wb_):
        _, vjp = jax.vjp(
            lambda w: direct_conv_blocked(xp_, w, key.stride, "VALID",
                                          precision=pol, groups=groups,
                                          dilation=dilation), wb_)
        return vjp(dy_)[0]
    return wgrad_jnp, (ops["dy"], ops["xp"], ops["wb"])


# ---------------------------------------------------------------------------
# the default dispatcher (checked-in table, lazy)
# ---------------------------------------------------------------------------

_DEFAULT: Optional[ConvDispatcher] = None


def get_dispatcher() -> ConvDispatcher:
    """The process-wide dispatcher over the checked-in table.  Call sites
    that don't pass their own ``dispatch=`` resolve through this one."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = ConvDispatcher.from_file()
    return _DEFAULT


def set_dispatcher(dispatcher: Optional[ConvDispatcher]) -> None:
    """Swap the process-wide dispatcher (None resets to the checked-in
    table on next use) — test seam and serving-config hook."""
    global _DEFAULT
    _DEFAULT = dispatcher
