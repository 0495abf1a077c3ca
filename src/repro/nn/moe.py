"""Mixture-of-Experts with capacity-bounded expert-parallel dispatch.

Distribution (see DESIGN.md §5): the mesh "model" axis of size M factors into
``ep = gcd(E, M)`` expert-parallel groups × ``tp = M // ep`` tensor-parallel
ranks *inside* each expert (mixtral: 8 experts on a 16-way axis -> ep=8,
tp=2; qwen3: ep=16, 8 local experts; jamba: ep=16).  Activations arrive
replicated over "model" (Megatron convention); every rank runs the identical
router, selects tokens destined to *its* experts into capacity-C buffers, and
one psum over "model" sums expert contributions and intra-expert TP partials
in a single collective — the same slot dense TP uses.

Expert weights are stored **device-major**: ``[ep*tp, le, d, f_loc]`` where
shard r holds experts ``[ (r//tp)*le, ... )`` and f-slice ``r % tp``.  The
shard dim is therefore always divisible by the model axis — no replicated
expert weights even when E < M (mixtral).  ``canonical_experts`` recovers the
logical ``[E, d, f]`` view for tests/export.

Dispatch never materializes a [T, E, C] one-hot tensor nor a [T*k, D] token
copy (the paper's no-packing discipline): the k router slots are processed
sequentially (slot 0 = highest router weight gets capacity first, GShard
priority semantics), each as one scatter-add of the resident [T, D] tokens.

The ``dense`` path (all experts, exact weighting, no drops) is the oracle the
distributed path is tested against (capacity -> inf makes them equal).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from .module import ParamSpec, Parallelism

__all__ = ["MoE", "router_topk", "canonical_experts"]


def router_topk(logits: jnp.ndarray, cfg: MoEConfig, axes=None):
    """-> (weights [T,k] f32, idx [T,k] int32, aux+z loss scalar).

    ``axes``: mesh axis names the tokens are sharded over — router statistics
    (occupancy/prob means, z-loss) are psum'd so the aux loss is the *global*
    Switch-style load-balance loss, identical to the single-device oracle.
    """
    lf = logits.astype(jnp.float32)
    if cfg.router_norm == "topk_softmax":
        # mixtral/jamba: select top-k logits, softmax over the selection
        w, idx = jax.lax.top_k(lf, cfg.top_k)
        w = jax.nn.softmax(w, axis=-1)
    else:
        # qwen3: softmax over all experts, renormalized top-k
        probs = jax.nn.softmax(lf, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance loss + router z-loss (global statistics)
    probs = jax.nn.softmax(lf, axis=-1)
    t, e = lf.shape
    occupancy = jnp.zeros((t, e), jnp.float32)
    occupancy = occupancy.at[jnp.arange(t)[:, None], idx].set(1.0)
    occ_sum = occupancy.sum(0)
    prob_sum = probs.sum(0)
    zsq_sum = jnp.sum(jax.nn.logsumexp(lf, axis=-1) ** 2)
    tot = jnp.asarray(t, jnp.float32)
    if axes:
        occ_sum = jax.lax.psum(occ_sum, axes)
        prob_sum = jax.lax.psum(prob_sum, axes)
        zsq_sum = jax.lax.psum(zsq_sum, axes)
        tot = jax.lax.psum(tot, axes)
    aux = e * jnp.sum((occ_sum / tot) * (prob_sum / tot)) * cfg.aux_loss_weight
    z = (zsq_sum / tot) * cfg.z_loss_weight
    return w, idx, aux + z


def canonical_experts(stored: jnp.ndarray, e: int, f: int,
                      kind: str) -> jnp.ndarray:
    """[ep*tp, le, d_or_floc, ...] device-major -> logical [E, d, f] / [E, f, d]."""
    eptp, le = stored.shape[:2]
    ep = e // le
    tp = eptp // ep
    if kind in ("gate", "up"):                      # [ep*tp, le, d, f_loc]
        d = stored.shape[2]
        x = stored.reshape(ep, tp, le, d, f // tp)
        return x.transpose(0, 2, 3, 1, 4).reshape(e, d, f)
    d = stored.shape[3]                             # down: [ep*tp, le, f_loc, d]
    x = stored.reshape(ep, tp, le, f // tp, d)
    return x.transpose(0, 2, 1, 3, 4).reshape(e, f, d)


def stored_from_canonical(canon: jnp.ndarray, ep: int, tp: int,
                          kind: str) -> jnp.ndarray:
    """Logical [E,d,f] / [E,f,d] -> device-major [ep*tp, le, ...]."""
    if kind in ("gate", "up"):
        e, d, f = canon.shape
        le, fl = e // ep, f // tp
        x = canon.reshape(ep, le, d, tp, fl).transpose(0, 3, 1, 2, 4)
        return x.reshape(ep * tp, le, d, fl)
    e, f, d = canon.shape
    le, fl = e // ep, f // tp
    x = canon.reshape(ep, le, tp, fl, d).transpose(0, 2, 1, 3, 4)
    return x.reshape(ep * tp, le, fl, d)


def convert_expert_layout(x: jnp.ndarray, kind: str, e: int, f: int,
                          dst_ep: int, dst_tp: int) -> jnp.ndarray:
    """Re-factor stored expert weights between mesh layouts (elastic restore).

    Handles extra leading dims (the stacked-layers axis) by vmapping.
    """
    def fn(a):
        return stored_from_canonical(
            canonical_experts(a, e, f, kind), dst_ep, dst_tp, kind)
    ndim = x.ndim
    while ndim > 4:
        fn = jax.vmap(fn)
        ndim -= 1
    return fn(x)


def remap_expert_tree(params, cfg: MoEConfig, dst_ep: int, dst_tp: int):
    """Walk a params tree, re-factoring every MoE expert subtree in place."""
    def walk(node):
        if isinstance(node, dict) and {"gate", "up", "down", "router"} <= set(node):
            out = dict(node)
            for kind in ("gate", "up", "down"):
                out[kind] = {"w": convert_expert_layout(
                    node[kind]["w"], kind, cfg.n_experts, cfg.d_ff,
                    dst_ep, dst_tp)}
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(params)


@dataclasses.dataclass(frozen=True)
class MoE:
    d_model: int
    cfg: MoEConfig
    ep: int = 1                 # expert-parallel groups (gcd(E, model))
    tp: int = 1                 # f-slices per expert (model // ep)

    @staticmethod
    def create(d_model: int, cfg: MoEConfig, px: Parallelism) -> "MoE":
        m = px.model_size
        ep = math.gcd(cfg.n_experts, m)
        return MoE(d_model, cfg, ep=ep, tp=m // ep)

    @property
    def le(self) -> int:
        return self.cfg.n_experts // self.ep

    @property
    def f_loc(self) -> int:
        assert self.cfg.d_ff % self.tp == 0
        return self.cfg.d_ff // self.tp

    def specs(self):
        d, m = self.d_model, self.ep * self.tp
        le, fl = self.le, self.f_loc
        ax = ("expert", None, None, None)
        return {
            "router": {"w": ParamSpec((d, self.cfg.n_experts), ("embed", None))},
            "gate": {"w": ParamSpec((m, le, d, fl), ax)},
            "up": {"w": ParamSpec((m, le, d, fl), ax)},
            "down": {"w": ParamSpec((m, le, fl, d), ax)},
        }

    # ------------------------------------------------------------------
    def _ffn(self, x, gate_w, up_w, down_w):
        """Batched expert FFN.  x: [le, C, D] -> [le, C, D] (partial if TP)."""
        g = jnp.einsum("ecd,edf->ecf", x, gate_w.astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", x, up_w.astype(x.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return jnp.einsum("ecf,efd->ecd", h, down_w.astype(x.dtype))

    def _expert_block(self, x2, weights, idx, gate_w, up_w, down_w,
                      e_lo, le: int, capacity: int, compact: bool = False):
        """Capacity dispatch -> FFN -> combine for experts [e_lo, e_lo+le).

        x2: [T, D]; weights/idx: [T, k].  Never materializes more than one
        [T, D]-sized intermediate per router slot.  ``compact``: accumulate
        the k-way combine in bf16 (halves the dominant [T,k,D] traffic;
        top-k weights sum to 1 so the error is one bf16 ulp per term).
        """
        t, d = x2.shape
        k = idx.shape[1]
        dump = le * capacity                           # overflow slot
        buf = jnp.zeros((dump + 1, d), x2.dtype)
        counts = jnp.zeros((le,), jnp.int32)
        slots, keeps = [], []
        erange = jnp.arange(le, dtype=jnp.int32)
        for j in range(k):                             # k static & small
            local = idx[:, j] - e_lo                   # [T]
            in_local = (local >= 0) & (local < le)
            oh = (local[:, None] == erange[None, :]) & in_local[:, None]
            ohi = oh.astype(jnp.int32)
            pos = counts[None, :] + jnp.cumsum(ohi, axis=0)   # 1-based
            entry_pos = jnp.sum(pos * ohi, axis=1)            # [T]
            keep = in_local & (entry_pos <= capacity)
            slot = jnp.where(keep,
                             jnp.clip(local, 0, le - 1) * capacity + entry_pos - 1,
                             dump)
            buf = buf.at[slot].add(x2 * keep[:, None].astype(x2.dtype))
            counts = counts + ohi.sum(0)
            slots.append(slot)
            keeps.append(keep)

        out = self._ffn(buf[:dump].reshape(le, capacity, d),
                        gate_w, up_w, down_w)
        flat = jnp.concatenate(
            [out.reshape(dump, d), jnp.zeros((1, d), out.dtype)], axis=0)
        acc_dtype = x2.dtype if compact else jnp.float32
        y = jnp.zeros((t, d), acc_dtype)
        for j in range(k):
            contrib = flat[jnp.where(keeps[j], slots[j], dump)]
            wj = (weights[:, j:j + 1] * keeps[j][:, None]).astype(acc_dtype)
            y = y + wj * contrib.astype(acc_dtype)
        return y.astype(jnp.float32)

    # ------------------------------------------------------------------
    def __call__(self, p, x: jnp.ndarray, px: Parallelism,
                 train: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [B, S, D] (replicated over model) -> (y, aux_loss)."""
        if px.mesh is None or px.model_size == 1:
            return self._dense(p, x)
        assert self.ep * self.tp == px.model_size, (self.ep, self.tp, px.model_size)

        b, s, d = x.shape
        cfg = self.cfg
        cf = cfg.capacity_factor if train else cfg.eval_capacity_factor
        le, tp = self.le, self.tp

        bspec = px.batch_spec(b)
        bsz = 1
        for a in (bspec or ()):
            bsz *= px.axis_size(a)
        t_loc = (b // bsz) * s
        capacity = max(4, -(-int(t_loc * cfg.top_k * cf) // cfg.n_experts))

        def inner(x, rw, gate_w, up_w, down_w):
            bl, s_, d_ = x.shape
            x2 = x.reshape(bl * s_, d_)
            logits = x2.astype(jnp.float32) @ rw.astype(jnp.float32)
            weights, idx, aux = router_topk(logits, cfg, axes=bspec)
            rank = jax.lax.axis_index("model")
            e_lo = (rank // tp) * le
            y = self._expert_block(x2, weights, idx, gate_w[0], up_w[0],
                                   down_w[0], e_lo, le, capacity,
                                   compact=bool(px.rules.get("moe_compact")))
            # expert groups are disjoint, and TP ranks hold disjoint f-slices
            # (elementwise silu*up is exact per-slice), so one psum combines
            # expert sums and TP partials exactly once.
            y = jax.lax.psum(y, "model")
            return y.reshape(bl, s_, d_).astype(x.dtype), aux

        wspec = P("model", None, None, None)
        y, aux = jax.shard_map(
            inner, mesh=px.mesh,
            in_specs=(P(bspec), P(None, None), wspec, wspec, wspec),
            out_specs=(P(bspec), P()),
            check_vma=False,
        )(x, p["router"]["w"], p["gate"]["w"], p["up"]["w"], p["down"]["w"])
        return y, aux

    # ------------------------------------------------------------------
    def _dense(self, p, x):
        """Oracle: every expert computes every token; exact combine weights."""
        b, s, d = x.shape
        e, f = self.cfg.n_experts, self.cfg.d_ff
        gate = canonical_experts(p["gate"]["w"], e, f, "gate")
        up = canonical_experts(p["up"]["w"], e, f, "up")
        down = canonical_experts(p["down"]["w"], e, f, "down")
        x2 = x.reshape(-1, d)
        logits = x2.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
        weights, idx, aux = router_topk(logits, self.cfg)
        w_full = jnp.zeros((x2.shape[0], e), jnp.float32)
        w_full = w_full.at[jnp.arange(x2.shape[0])[:, None], idx].add(weights)
        h = self._ffn(jnp.broadcast_to(x2, (e,) + x2.shape), gate, up, down)
        y = jnp.einsum("te,etd->td", w_full, h.astype(jnp.float32))
        return y.reshape(b, s, d).astype(x.dtype), aux
