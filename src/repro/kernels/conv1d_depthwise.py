"""Pallas TPU kernel: causal depthwise conv1d (the Mamba/Jamba short conv).

This is the paper's direct-convolution idea specialized to the depthwise-1d
convolutions inside SSM blocks: channel-blocked layout [B, D/Db, L, Db] with
Db = 128 (lanes), sequence as sublanes, and the K-tap convolution computed as
K shifted multiply-adds on VMEM-resident views — no patch matrix, zero memory
overhead.

Cross-block causality trick: each grid step reads *two* views of the same
input array — the current sequence block and the previous one (BlockSpecs may
alias the same operand with different index maps).  The kernel takes the last
K-1 rows of the previous block as the causal tail; for the first block the
tail is masked to zero.  This keeps every load a contiguous BlockSpec copy —
no halo DMAs, no overlapping blocks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_machine
from .conv2d_common import compiler_params

__all__ = ["conv1d_depthwise_blocked_pallas"]


def _kernel(xc_ref, xp_ref, w_ref, o_ref, *, k, lb):
    l_idx = pl.program_id(2)
    cur = xc_ref[0, 0]                                  # (Lb, Db)
    tail = xp_ref[0, 0, lb - (k - 1):, :]               # (K-1, Db)
    tail = jnp.where(l_idx > 0, tail, jnp.zeros_like(tail))
    acc = jnp.zeros(cur.shape, jnp.float32)
    # xwin[i] = concat(tail, cur)[i : i+Lb]; unrolled K-tap shift-and-add.
    for i in range(k):
        if i < k - 1:
            shifted = jnp.concatenate([tail[i:], cur[:lb - (k - 1 - i)]], axis=0)
        else:
            shifted = cur
        acc = acc + shifted.astype(jnp.float32) * w_ref[i, 0].astype(jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("lb", "interpret"))
def conv1d_depthwise_blocked_pallas(x: jnp.ndarray, w: jnp.ndarray,
                                    lb: int = 512,
                                    interpret: bool = False) -> jnp.ndarray:
    """x: [B, D/Db, L, Db]; w: [K, D/Db, Db] -> same shape as x (causal)."""
    b, dblk, l, db = x.shape
    k, dblk2, db2 = w.shape
    assert (dblk, db) == (dblk2, db2), (x.shape, w.shape)
    lb = min(lb, l)
    assert l % lb == 0, f"L={l} must be divisible by block {lb}"
    assert lb >= k - 1, f"sequence block {lb} must cover the {k - 1} causal taps"

    grid = (b, dblk, l // lb)
    return pl.pallas_call(
        partial(_kernel, k=k, lb=lb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, lb, db), lambda b_, d, li: (b_, d, li, 0)),
            # previous sequence block of the SAME array (clamped at 0)
            pl.BlockSpec((1, 1, lb, db),
                         lambda b_, d, li: (b_, d, jnp.maximum(li - 1, 0), 0)),
            pl.BlockSpec((k, 1, db), lambda b_, d, li: (0, d, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, lb, db), lambda b_, d, li: (b_, d, li, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compiler_params(resolve_machine(None),
                                        ("parallel",) * 3),
        interpret=interpret,
    )(x, x, w)
