"""Minimal functional module system: ParamSpec trees + logical sharding axes.

No flax/haiku in this environment — and none needed: a layer is a plain
object exposing ``specs() -> {name: ParamSpec | subtree}`` and
``__call__(params, ...)``.  ``ParamSpec.axes`` names each dimension with a
*logical* axis ("embed", "heads", "vocab", ...) which ``Parallelism`` maps to
mesh axes with divisibility checking — the single place sharding decisions
live.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Optional[str]
SpecTree = Union["ParamSpec", Dict[str, Any]]

__all__ = [
    "ParamSpec", "init_tree", "axes_tree", "count_params",
    "Parallelism", "DEFAULT_RULES", "with_layers_axis",
]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Axis, ...]
    init: str = "fan_in"            # fan_in | normal | zeros | ones
    scale: float = 1.0              # multiplier (normal: stddev)
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_one(spec: ParamSpec, key) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        return (spec.scale * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    if spec.init == "fan_in":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (std * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def _fold_path(key, path: str):
    # crc32, not hash(): str hashes are randomized per process, so the same
    # seed would give different weights in every run
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def init_tree(specs: SpecTree, key, path: str = "") -> Any:
    """Deterministic per-path initialization (stable under tree edits)."""
    if isinstance(specs, ParamSpec):
        return _init_one(specs, _fold_path(key, path))
    return {k: init_tree(v, key, f"{path}/{k}") for k, v in specs.items()}


def axes_tree(specs: SpecTree) -> Any:
    if isinstance(specs, ParamSpec):
        return specs.axes
    return {k: axes_tree(v) for k, v in specs.items()}


def count_params(specs: SpecTree) -> int:
    if isinstance(specs, ParamSpec):
        return int(np.prod(specs.shape))
    return sum(count_params(v) for v in specs.values())


def with_layers_axis(specs: SpecTree, n: int, axis_name: Axis = "layers") -> Any:
    """Prepend a stacked-layers dimension to every spec (for lax.scan)."""
    if isinstance(specs, ParamSpec):
        return ParamSpec((n,) + specs.shape, (axis_name,) + specs.axes,
                         specs.init, specs.scale, specs.dtype)
    return {k: with_layers_axis(v, n, axis_name) for k, v in specs.items()}


# ---------------------------------------------------------------------------
# Parallelism: logical axis -> mesh axis rules, with divisibility fallback
# ---------------------------------------------------------------------------

# Activations stay replicated over "model" between ops (Megatron-style);
# weights shard per these rules; XLA inserts the matching collectives.
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "batch": ("pod", "data"),     # pruned to existing mesh axes automatically
    "embed": None,
    "mlp": "model",               # column/row parallel d_ff
    "heads": "model",             # q heads (padded to a multiple if needed)
    "kv_heads": "model",          # falls back to replicated if not divisible
    "vocab": "model",
    "vocab_in": "model",   # untied input tables; set None to replicate small ones
    "expert": "model",            # MoE expert-parallel dim
    "expert_mlp": None,           # intra-expert d_ff (sharded via shard_map tp)
    "kv_seq": "model",            # decode KV-cache sequence sharding
    "ssm_heads": "model",
    "layers": None,
    "seq": None,
    "act_seq": None,   # flip to "model" for Megatron-SP sequence sharding
    "conv_k": None,
    "d_state": None,
}


@dataclasses.dataclass
class Parallelism:
    """Mesh + logical->physical rules.  mesh=None means single-device tests."""

    mesh: Optional[Mesh] = None
    rules: Dict[str, Union[str, Tuple[str, ...], None]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))

    # -- mesh introspection ------------------------------------------------
    def axis_size(self, name: str) -> int:
        if self.mesh is None or name not in self.mesh.shape:
            return 1
        return self.mesh.shape[name]

    @property
    def model_size(self) -> int:
        return self.axis_size("model")

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        spec = self.rules.get("batch", ())
        if spec is None:
            return ()
        axes = (spec,) if isinstance(spec, str) else tuple(spec)
        return tuple(a for a in axes if self.axis_size(a) > 1 or
                     (self.mesh is not None and a in self.mesh.shape))

    def _physical(self, logical: Axis) -> Tuple[str, ...]:
        if logical is None:
            return ()
        rule = self.rules.get(logical, None)
        if rule is None:
            return ()
        axes = (rule,) if isinstance(rule, str) else tuple(rule)
        return tuple(a for a in axes if self.mesh is not None and a in self.mesh.shape)

    # -- spec construction -------------------------------------------------
    def pspec(self, axes: Sequence[Axis], shape: Sequence[int]) -> P:
        """Logical axes -> PartitionSpec; replicate any non-divisible dim."""
        out = []
        used = set()
        for ax, dim in zip(axes, shape):
            phys = tuple(a for a in self._physical(ax) if a not in used)
            total = int(np.prod([self.axis_size(a) for a in phys])) if phys else 1
            if phys and dim % total == 0:
                out.append(phys if len(phys) > 1 else phys[0])
                used.update(phys)
            else:
                out.append(None)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def named_sharding(self, axes: Sequence[Axis], shape: Sequence[int]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(axes, shape))

    def constrain(self, x: jnp.ndarray, *axes: Axis) -> jnp.ndarray:
        """with_sharding_constraint under a mesh; identity otherwise."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.pspec(axes, x.shape)))

    def param_shardings(self, specs: SpecTree) -> Any:
        """NamedSharding tree matching ``init_tree`` output (None w/o mesh).

        With rules["fsdp"] set, parameters are additionally sharded over the
        data axis on their largest still-unsharded divisible dim (ZeRO-3 /
        FSDP): required for >100B models (qwen3-235B: 29 GiB/chip of bf16
        params under model-only sharding vs 16 GiB HBM).  XLA re-gathers each
        layer's weights at use — the standard FSDP traffic/memory trade.
        """
        if isinstance(specs, ParamSpec):
            if self.mesh is None:
                return None
            pspec = self.pspec(specs.axes, specs.shape)
            if self.rules.get("fsdp") and "data" in self.mesh.shape:
                parts = list(pspec) + [None] * (len(specs.shape) - len(pspec))
                used = {a for pp in parts if pp
                        for a in ((pp,) if isinstance(pp, str) else pp)}
                if ("data" not in used
                        and int(np.prod(specs.shape)) >= 2 ** 16):
                    dsize = self.axis_size("data")
                    cands = [(dim, i) for i, (dim, part) in
                             enumerate(zip(specs.shape, parts))
                             if part is None and dim % dsize == 0]
                    if cands:
                        _, i = max(cands)
                        parts[i] = "data"
                        pspec = P(*parts)
            return NamedSharding(self.mesh, pspec)
        return {k: self.param_shardings(v) for k, v in specs.items()}

    def batch_spec(self, batch_size: int):
        """Mesh axes to shard a batch of this size over (greedy suffix
        fallback: (pod,data) -> (data,) -> None when not divisible) — used by
        shard_map segments, which require exact divisibility."""
        axes = list(self.batch_axes)
        while axes:
            total = 1
            for a in axes:
                total *= self.axis_size(a)
            if batch_size % total == 0:
                return tuple(axes)
            axes.pop(0)
        return None

    # -- utility -----------------------------------------------------------
    def pad_to_axis(self, n: int, logical: str) -> int:
        """Round ``n`` up to a multiple of the axis extent (head padding)."""
        phys = self._physical(logical)
        total = int(np.prod([self.axis_size(a) for a in phys])) if phys else 1
        return -(-n // total) * total
