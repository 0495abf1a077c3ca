"""Attention: GQA + RoPE + sliding-window + soft-capping + cross-attention,
with a chunked (flash-style, online-softmax) evaluator for long sequences and
a sequence-sharded flash-decode path for serving.

Distribution:
  * train/prefill — q heads sharded over "model" (padded to a multiple when
    H % model != 0, e.g. deepseek-coder's 56 heads -> 64 slots; padded slots
    are masked to zero so the math is exactly the unpadded model's);
    kv heads sharded iff divisible, else replicated (they are small).
  * decode — the KV cache is sharded over the *sequence* dim ("kv_seq" ->
    "model"); a shard_map computes per-shard partial (max, denom, value) and
    merges with pmax/psum — flash-decode.  This is what makes 500k-token
    caches fit, and works for any head count.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import rope, softcap
from .module import ParamSpec, Parallelism

__all__ = ["Attention", "attend", "KVCache", "init_kv_cache"]

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Ring-buffer KV cache for one layer group.  k/v: [B, W, KV, Dh]."""
    k: jnp.ndarray
    v: jnp.ndarray


def init_kv_cache(batch: int, window: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, window, n_kv, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------

def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           q_positions: jnp.ndarray, kv_positions: jnp.ndarray,
           causal: bool = True, window: Optional[int] = None,
           cap: Optional[float] = None, scale: float,
           kv_valid: Optional[jnp.ndarray] = None,
           chunk: int = 2048, compact_probs: bool = False,
           unroll: bool = False) -> jnp.ndarray:
    """q: [B,Sq,KV,G,Dh] grouped; k/v: [B,Skv,KV,Dh] -> [B,Sq,KV,G,Dh].

    Scans KV in chunks with an online softmax: peak memory is O(Sq * chunk)
    instead of O(Sq * Skv) — the paper's no-packed-intermediate philosophy
    applied to attention (the full score matrix is never materialized).
    """
    b, sq, nkv, g, dh = q.shape
    skv = k.shape[1]
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-(10 ** 9))
    kc = k.reshape(b, n_chunks, chunk, nkv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, nkv, dh).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    qf = q if compact_probs else q.astype(jnp.float32)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp
        # compact_probs: keep every [.., C]-sized intermediate (scores,
        # probs) in bf16 storage — the dominant attention buffers; softmax
        # statistics (m, l) and the output accumulator stay f32 (one bf16
        # ulp of error on scores/probs; flash TPU kernels keep these in
        # VMEM — this is the storage-dtype analogue).
        sdt = jnp.bfloat16 if compact_probs else jnp.float32
        s = jnp.einsum("bskgd,bckd->bskgc", qf,
                       kb if compact_probs else kb.astype(jnp.float32),
                       preferred_element_type=sdt) * jnp.asarray(scale, sdt)
        s = softcap(s, cap)
        valid = pb[:, None, :] >= 0                                   # [B,Sq,C]
        if kv_valid is not None:
            valid = valid & (pb[:, None, :] < kv_valid[:, None, None])
        if causal:
            valid = valid & (pb[:, None, :] <= q_positions[:, :, None])
        if window is not None:
            valid = valid & (pb[:, None, :] > q_positions[:, :, None] - window)
        s = jnp.where(valid[:, :, None, None, :], s, jnp.asarray(NEG_INF, sdt))
        m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None].astype(sdt))                 # sdt
        l_new = l * alpha + p.sum(axis=-1, dtype=jnp.float32)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p,
            vb if compact_probs else vb.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), ()

    m0 = jnp.full((b, sq, nkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, nkv, g), jnp.float32)
    a0 = jnp.zeros((b, sq, nkv, g, dh), jnp.float32)
    if unroll:
        # python loop (cost extraction: scan bodies are counted once by
        # XLA cost analysis — see launch/dryrun.py)
        carry = (m0, l0, a0)
        for i in range(n_chunks):
            carry, _ = step(carry, (kc[i], vc[i], pc[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-decode over a sequence-sharded ring cache
# ---------------------------------------------------------------------------

def _decode_update_and_attend(q, k_new, v_new, ck, cv, pos, *,
                              window: Optional[int], cap, scale,
                              seq_shards: int, axis: Optional[str]):
    """Body shared by the shard_map and single-device decode paths.

    q: [B,KV,G,Dh]; k_new/v_new: [B,KV,Dh]; ck/cv: [B, W_local, KV, Dh]
    (the local shard of a [B, W] ring buffer); pos: scalar int32 —
    the index of the token being written (global step count).
    """
    b, w_loc, nkv, dh = ck.shape
    w_total = w_loc * seq_shards
    shard = jax.lax.axis_index(axis) if axis else 0
    slot = pos % w_total
    local_slot = slot - shard * w_loc
    in_range = (local_slot >= 0) & (local_slot < w_loc)
    li = jnp.clip(local_slot, 0, w_loc - 1)
    ck = jnp.where(in_range, jax.lax.dynamic_update_slice(
        ck, k_new[:, None].astype(ck.dtype), (0, li, 0, 0)), ck)
    cv = jnp.where(in_range, jax.lax.dynamic_update_slice(
        cv, v_new[:, None].astype(cv.dtype), (0, li, 0, 0)), cv)

    # validity: ring slot j holds global position p(j) = pos - ((slot - j) mod W)
    j = shard * w_loc + jax.lax.iota(jnp.int32, w_loc)
    age = jnp.mod(slot - j, w_total)
    gpos = pos - age
    valid = gpos >= 0
    if window is not None:
        valid = valid & (gpos > pos - window)

    s = jnp.einsum("bkgd,bwkd->bkgw", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) * scale
    s = softcap(s, cap)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m_loc = s.max(axis=-1)
    p = jnp.exp(s - m_loc[..., None])
    l_loc = p.sum(axis=-1)
    o_loc = jnp.einsum("bkgw,bwkd->bkgd", p, cv.astype(jnp.float32))
    if axis:
        m_g = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, axis)
        o_g = jax.lax.psum(o_loc * corr[..., None], axis)
    else:
        l_g, o_g = l_loc, o_loc
    out = o_g / jnp.maximum(l_g[..., None], 1e-37)
    return out.astype(q.dtype), ck, cv


def flash_decode(q, k_new, v_new, cache: KVCache, pos, *, window, cap, scale,
                 px: Parallelism) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step against a (possibly sequence-sharded) ring cache."""
    n_shards = px.model_size
    if px.mesh is None or n_shards == 1:
        out, ck, cv = _decode_update_and_attend(
            q, k_new, v_new, cache.k, cache.v, pos, window=window, cap=cap,
            scale=scale, seq_shards=1, axis=None)
        return out, KVCache(ck, cv)

    bs = px.batch_spec(q.shape[0])

    def inner(q, k_new, v_new, ck, cv, pos):
        out, ck, cv = _decode_update_and_attend(
            q, k_new, v_new, ck, cv, pos[0], window=window, cap=cap,
            scale=scale, seq_shards=n_shards, axis="model")
        return out, ck, cv

    out, ck, cv = jax.shard_map(
        inner, mesh=px.mesh,
        in_specs=(P(bs), P(bs), P(bs), P(bs, "model"), P(bs, "model"), P()),
        out_specs=(P(bs), P(bs, "model"), P(bs, "model")),
        check_vma=False,
    )(q, k_new, v_new, cache.k, cache.v, pos[None])
    return out, KVCache(ck, cv)


# ---------------------------------------------------------------------------
# The attention module
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Attention:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    padded_heads: int                  # n_heads rounded up for TP
    rope_theta: float = 10000.0
    use_rope: bool = True
    qk_norm: bool = False
    use_bias: bool = False
    scale: Optional[float] = None
    cross: bool = False
    norm_eps: float = 1e-6

    @property
    def _scale(self) -> float:
        return self.scale if self.scale is not None else self.head_dim ** -0.5

    @property
    def groups(self) -> int:
        return self.padded_heads // self.n_kv_heads

    def specs(self):
        d, dh = self.d_model, self.head_dim
        hp, kv = self.padded_heads, self.n_kv_heads
        s = {
            "q": {"w": ParamSpec((d, hp, dh), ("embed", "heads", None))},
            "k": {"w": ParamSpec((d, kv, dh), ("embed", "kv_heads", None))},
            "v": {"w": ParamSpec((d, kv, dh), ("embed", "kv_heads", None))},
            "o": {"w": ParamSpec((hp, dh, d), ("heads", None, "embed"))},
        }
        if self.use_bias:
            s["q"]["b"] = ParamSpec((hp, dh), ("heads", None), init="zeros")
            s["k"]["b"] = ParamSpec((kv, dh), ("kv_heads", None), init="zeros")
            s["v"]["b"] = ParamSpec((kv, dh), ("kv_heads", None), init="zeros")
            s["o"]["b"] = ParamSpec((d,), ("embed",), init="zeros")
        if self.qk_norm:
            s["q_norm"] = {"w": ParamSpec((dh,), (None,), init="ones")}
            s["k_norm"] = {"w": ParamSpec((dh,), (None,), init="ones")}
        return s

    # -- helpers -----------------------------------------------------------
    def _head_mask(self) -> Optional[jnp.ndarray]:
        """Zero-mask for padded q-head slots (group-major layout)."""
        if self.padded_heads == self.n_heads:
            return None
        slots = self.groups
        real = self.n_heads // self.n_kv_heads
        j = jnp.arange(self.padded_heads) % slots
        return (j < real).astype(jnp.float32)

    def _norm(self, w, x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + self.norm_eps)
                * w.astype(jnp.float32)).astype(x.dtype)

    def _project(self, p, x, which: str, n: int):
        w = p[which]["w"].astype(x.dtype)
        y = jnp.einsum("bsd,dhe->bshe", x, w)
        if self.use_bias:
            y = y + p[which]["b"].astype(x.dtype)
        return y

    def qkv(self, p, x, kv_src, positions, kv_positions, px: Parallelism):
        b, s, _ = x.shape
        q = self._project(p, x, "q", self.padded_heads)
        k = self._project(p, kv_src, "k", self.n_kv_heads)
        v = self._project(p, kv_src, "v", self.n_kv_heads)
        if self.qk_norm:
            q = self._norm(p["q_norm"]["w"], q)
            k = self._norm(p["k_norm"]["w"], k)
        if self.use_rope and not self.cross:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, kv_positions, self.rope_theta)
        q = px.constrain(q, "batch", None, "heads", None)
        return q, k, v

    def output(self, p, ctx, px: Parallelism):
        """ctx: [B,S,Hp,Dh] -> o-projection (row-parallel)."""
        mask = self._head_mask()
        if mask is not None:
            ctx = ctx * mask[None, None, :, None].astype(ctx.dtype)
        y = jnp.einsum("bshe,hed->bsd", ctx, p["o"]["w"].astype(ctx.dtype))
        if self.use_bias:
            y = y + p["o"]["b"].astype(ctx.dtype)
        return px.constrain(y, "batch", "act_seq", "embed")

    # -- full paths ----------------------------------------------------------
    def __call__(self, p, x, *, positions, px: Parallelism, causal=True,
                 window=None, cap=None, kv=None, kv_positions=None,
                 kv_valid=None, chunk=2048, unroll=False):
        """Train / prefill / encoder / cross attention."""
        kv_src = kv if self.cross else x
        if kv_positions is None:
            kv_positions = (jnp.zeros(kv_src.shape[:2], jnp.int32) if self.cross
                            else positions)
        q, k, v = self.qkv(p, x, kv_src, positions, kv_positions, px)
        b, s, hp, dh = q.shape
        qg = q.reshape(b, s, self.n_kv_heads, self.groups, dh)
        ctx = attend(qg, k, v, q_positions=positions, kv_positions=kv_positions,
                     causal=causal and not self.cross, window=window, cap=cap,
                     scale=self._scale, kv_valid=kv_valid, chunk=chunk,
                     compact_probs=bool(px.rules.get("attn_bf16")),
                     unroll=unroll)
        return self.output(p, ctx.reshape(b, s, hp, dh), px)

    def from_kv(self, p, x, k, v, *, positions, px: Parallelism, cap=None):
        """Cross-attention against precomputed K/V (decode path)."""
        b, s, _ = x.shape
        q = self._project(p, x, "q", self.padded_heads)
        if self.qk_norm:
            q = self._norm(p["q_norm"]["w"], q)
        q = px.constrain(q, "batch", None, "heads", None)
        qg = q.reshape(b, s, self.n_kv_heads, self.groups, self.head_dim)
        kv_positions = jnp.zeros(k.shape[:2], jnp.int32)
        ctx = attend(qg, k, v, q_positions=positions, kv_positions=kv_positions,
                     causal=False, cap=cap, scale=self._scale)
        return self.output(p, ctx.reshape(b, s, self.padded_heads,
                                          self.head_dim), px)

    def decode(self, p, x, cache: KVCache, pos, *, px: Parallelism,
               window=None, cap=None):
        """One-token step.  x: [B, 1, D]; pos: scalar int32 global position."""
        b = x.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        q, k, v = self.qkv(p, x, x, positions, positions, px)
        qg = q.reshape(b, self.n_kv_heads, self.groups, self.head_dim)
        ctx, new_cache = flash_decode(
            qg, k[:, 0], v[:, 0], cache, pos, window=window, cap=cap,
            scale=self._scale, px=px)
        ctx = ctx.reshape(b, 1, self.padded_heads, self.head_dim)
        return self.output(p, ctx, px), new_cache
