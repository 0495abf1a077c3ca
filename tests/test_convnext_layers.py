"""The ConvNeXt layers on the blocked layout (``nn/conv.py``).

* ``ConvNeXtBlock`` folds its layer scale into the second pointwise conv:
  output and every gradient, the layer scale's included, equal the
  unfolded block (scale after the conv, then the skip add) in f32;
* with ``gap`` the pool rides that conv's epilogue and includes the skip;
* ``PatchifyStem`` and ``Downsample`` keep the blocked layout and chain;
  ``BlockedCNN`` holds the head LayerNorm and bias among its parameters.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout as L
from repro.nn.conv import (BlockedCNN, BlockedLayerNorm, ConvNeXtBlock,
                           Downsample, PatchifyStem)
from repro.nn.module import init_tree


def _perturbed(specs, seed):
    """Parameters off their init, so no scale is 1 and no bias 0."""
    p = init_tree(specs, jax.random.PRNGKey(seed))
    leaves, tdef = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return tdef.unflatten([x + 0.2 * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])


def _unfolded(blk, p, xb, gap=False):
    h = blk.depthwise(p["dw"], xb)
    h = blk.norm(p["norm"], h)
    h = blk.pw1(p["pw1"], h)
    y = blk.pw2(p["pw2"], h)
    out = xb + p["gamma"][None, :, None, None, :] * y
    return L.blocked_to_nhwc(out).mean(axis=(1, 2)) if gap else out


def test_layer_scale_fold_matches_the_unfolded_block():
    blk = ConvNeXtBlock(c=16, lane=8)
    p = _perturbed(blk.specs(), 0)
    xb = L.nhwc_to_blocked(jax.random.normal(jax.random.PRNGKey(2),
                                             (2, 8, 8, 16)), 8)
    ct = jax.random.normal(jax.random.PRNGKey(3), xb.shape)
    np.testing.assert_allclose(blk(p, xb), _unfolded(blk, p, xb), atol=1e-5)
    got = jax.grad(lambda p, x: jnp.sum(blk(p, x) * ct), argnums=(0, 1))(
        p, xb)
    want = jax.grad(lambda p, x: jnp.sum(_unfolded(blk, p, x) * ct),
                    argnums=(0, 1))(p, xb)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_pool_rides_the_block_and_includes_the_skip():
    blk = ConvNeXtBlock(c=16, lane=8)
    p = _perturbed(blk.specs(), 4)
    xb = L.nhwc_to_blocked(jax.random.normal(jax.random.PRNGKey(5),
                                             (2, 4, 4, 16)), 8)
    pooled = blk(p, xb, gap=True)
    assert pooled.shape == (2, 16)
    np.testing.assert_allclose(pooled, _unfolded(blk, p, xb, gap=True),
                               atol=1e-5)


def test_stem_and_downsample_chain_and_the_head_is_the_models():
    model = BlockedCNN(
        convs=(PatchifyStem(ci=3, co=16, lane=8), ConvNeXtBlock(c=16, lane=8),
               Downsample(ci=16, co=24, lane=8), ConvNeXtBlock(c=24, lane=8)),
        n_classes=5, head_norm=BlockedLayerNorm(c=24, lane=8),
        head_bias=True)
    specs = model.specs()
    assert {"head_norm", "head_bias"} <= set(specs)
    p = _perturbed(specs, 6)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 16, 3))
    h = model.convs[0](p["conv0"], L.nhwc_to_blocked(x, 3))
    assert h.shape == (2, 2, 4, 4, 8)
    h = model.convs[2](p["conv2"], model.convs[1](p["conv1"], h))
    assert h.shape == (2, 3, 2, 2, 8)
    logits = model(p, x)
    assert logits.shape == (2, 5) and bool(jnp.all(jnp.isfinite(logits)))
