"""Pallas TPU kernel: flash attention (forward), GQA-aware.

This is the memory-term fix identified in EXPERIMENTS.md §Perf.2: the
chunked-attention score/prob tensors never leave VMEM — HBM traffic is
exactly Q + K + V + O, the roofline minimum.  Two paper-derived touches:

  * GQA without materializing repeated K/V: the K/V BlockSpec *index maps*
    send q-head ``h`` to kv-head ``h // group``; the repeat never exists in
    memory (the same zero-overhead trick as the conv kernels' layouts);
  * the online-softmax accumulators (m, l, acc) are the "register tile" of
    the paper's model — sized by the q-block so Eq. 2 (fit the fast memory)
    holds: Bq×Dh f32 + 2×Bq stats alongside one K/V block.

Grid: (B, H, Sq/Bq, Skv/Bk), kv innermost (the reduction dim, like the
conv kernel's Ci blocks).  Causality is enforced by position masking; blocks
strictly above the diagonal still execute masked (documented; a block-skip
is a TPU-side optimization via ``pl.when`` on the block index).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_machine
from .conv2d_common import compiler_params

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, bq, bk, n_kv_blocks, cap):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # [Bq, Dh]
    k = k_ref[0, 0].astype(jnp.float32)                  # [Bk, Dh]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_new = acc_prev * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...] = m_new, l_new
    acc_ref[...] = acc_new

    @pl.when(ik == n_kv_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_new / jnp.maximum(l_new, 1e-37)[:, None]
                       ).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("scale", "causal", "bq", "bk", "cap",
                                   "interpret"))
def flash_attention_pallas(q, k, v, *, scale: float, causal: bool = True,
                           bq: int = 512, bk: int = 512, cap=None,
                           interpret: bool = False):
    """q: [B, H, Sq, Dh]; k/v: [B, KV, Skv, Dh] (KV divides H) -> like q."""
    b, h, sq, dh = q.shape
    _, kv, skv, _ = k.shape
    assert h % kv == 0, (h, kv)
    g = h // kv
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    grid = (b, h, sq // bq, skv // bk)

    return pl.pallas_call(
        partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                n_kv_blocks=skv // bk, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            # GQA: index map folds q-head -> kv-head; no repeated K/V copies
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=compiler_params(
            resolve_machine(None), ("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(q, k, v)
