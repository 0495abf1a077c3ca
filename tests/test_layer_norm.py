"""LayerNorm over the channels of a blocked map (``kernels/layer_norm``).

* the Pallas forward and backward (interpret mode) against the jnp oracle
  and against ``jax.grad`` of a plain NHWC LayerNorm, in f32 and bf16, for
  pencils that split the channels over several blocks;
* lanes past ``lanes`` (a pencil padded for a launch) never enter the
  statistics: whatever they hold, the channels read as the unpadded map's,
  and the padded lanes of y and dx are zeros;
* ``BlockedLayerNorm`` keeps the blocked layout in and out, emits the
  policy's operand dtype, and normalises flat pooled features;
* the exact-GELU epilogue (``gelu_erf``) and its VJP through the pointwise
  kernel, against ``jax.nn.gelu(approximate=False)``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layout as L
from repro.core.direct_conv import apply_activation, erf_f32, gelu_erf
from repro.core.context import ConvContext
from repro.kernels.conv2d_pointwise import pointwise_conv2d_blocked_pallas
from repro.kernels.layer_norm import (layer_norm_blocked,
                                      layer_norm_blocked_pallas,
                                      layer_norm_bwd_pallas,
                                      layer_norm_blocked_ref)
from repro.core.blocking import CPU_HASWELL
from repro.nn.conv import BlockedLayerNorm

EPS = 1e-6


def _nhwc_ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * g + b


def _operands(c, cb, h=6, w=5, n=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = 2.0 + 3.0 * jax.random.normal(k[0], (n, h, w, c))
    g = 1.0 + 0.1 * jax.random.normal(k[1], (c,))
    b = 0.1 * jax.random.normal(k[2], (c,))
    return x, g, b, L.nhwc_to_blocked(x, cb), g.reshape(-1, cb), b.reshape(-1, cb)


@pytest.mark.parametrize("c,cb", [(24, 8), (24, 24), (40, 8)])
def test_forward_matches_nhwc_layer_norm(c, cb):
    x, g, b, xb, gb, bb = _operands(c, cb)
    y = layer_norm_blocked(xb, gb, bb, eps=EPS, interpret=True)
    want = _nhwc_ln(x, g, b)
    np.testing.assert_allclose(L.blocked_to_nhwc(y), want, atol=2e-5)
    np.testing.assert_allclose(
        y, layer_norm_blocked_ref(xb, gb, bb, eps=EPS), atol=2e-6)


@pytest.mark.parametrize("c,cb", [(24, 8), (16, 16)])
def test_backward_matches_autodiff(c, cb):
    x, g, b, xb, gb, bb = _operands(c, cb, seed=1)
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    ctb = L.nhwc_to_blocked(ct, cb)

    def kernel(xb, gb, bb):
        return jnp.sum(layer_norm_blocked(xb, gb, bb, eps=EPS,
                                          interpret=True) * ctb)

    def plain(x, g, b):
        return jnp.sum(_nhwc_ln(x, g, b) * ct)

    dxb, dgb, dbb = jax.grad(kernel, argnums=(0, 1, 2))(xb, gb, bb)
    dx, dg, db = jax.grad(plain, argnums=(0, 1, 2))(x, g, b)
    np.testing.assert_allclose(L.blocked_to_nhwc(dxb), dx, atol=2e-5)
    np.testing.assert_allclose(dgb.reshape(-1), dg, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(dbb.reshape(-1), db, rtol=1e-5, atol=2e-5)


def test_bf16_keeps_its_dtype_and_takes_f32_statistics():
    x, g, b, xb, gb, bb = _operands(32, 16, seed=2)
    xq = xb.astype(jnp.bfloat16)
    y = layer_norm_blocked(xq, gb, bb, eps=EPS, interpret=True)
    assert y.dtype == jnp.bfloat16
    want = layer_norm_blocked_ref(xq.astype(jnp.float32), gb, bb, eps=EPS)
    np.testing.assert_allclose(y.astype(jnp.float32), want, atol=2e-2)
    dx = jax.grad(lambda t: layer_norm_blocked(
        t, gb, bb, eps=EPS, interpret=True).astype(jnp.float32).sum())(xq)
    assert dx.dtype == jnp.bfloat16


def test_padded_lanes_never_enter_the_statistics():
    """Pencils of 6 channels carried in 8 lanes: whatever the two padded
    lanes hold, the statistics are the 6-channel pencils' own."""
    x, g, b, xb, gb, bb = _operands(18, 6, seed=3)
    junk = 50.0 + jax.random.normal(jax.random.PRNGKey(4),
                                    xb.shape[:-1] + (2,))
    xp = jnp.concatenate([xb, junk], axis=-1)
    gp, bp = (jnp.pad(t, ((0, 0), (0, 2))) for t in (gb, bb))
    kw = dict(lanes=6, eps=EPS, machine=CPU_HASWELL, interpret=True)
    y = layer_norm_blocked_pallas(xp, gp, bp, **kw)
    np.testing.assert_allclose(y[..., :6], layer_norm_blocked_ref(
        xb, gb, bb, eps=EPS), atol=2e-6)
    assert not np.any(np.asarray(y[..., 6:]))
    np.testing.assert_allclose(y, layer_norm_blocked_ref(
        xp, gp, bp, lanes=6, eps=EPS), atol=2e-6)

    dy = jax.random.normal(jax.random.PRNGKey(5), xb.shape)
    dyp = jnp.concatenate([dy, junk], axis=-1)
    dx, dg, db = layer_norm_bwd_pallas(xp, dyp, gp, **kw)
    _, vjp = jax.vjp(lambda t, g, b: layer_norm_blocked_ref(t, g, b, eps=EPS),
                     xb, gb, bb)
    wdx, wdg, wdb = vjp(dy)
    np.testing.assert_allclose(dx[..., :6], wdx, atol=2e-5)
    assert not np.any(np.asarray(dx[..., 6:]))
    np.testing.assert_allclose(dg[:, :6], wdg, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(db[:, :6], wdb, rtol=1e-5, atol=2e-5)


def test_layer_keeps_the_blocked_layout_and_the_policy_dtype():
    ln = BlockedLayerNorm(c=24, lane=8, precision="bf16")
    x, g, b, xb, gb, bb = _operands(24, 8, seed=6)
    p = {"g": gb, "b": bb}
    y = ln(p, xb)
    assert y.shape == xb.shape and y.dtype == jnp.bfloat16
    yj = ln(p, xb, context=ConvContext(impl="jnp"))
    np.testing.assert_allclose(y.astype(jnp.float32),
                               yj.astype(jnp.float32), atol=1e-2)
    feat = x[:, 0, 0, :]                                 # flat [N, C]
    yf = BlockedLayerNorm(c=24, lane=8)(p, feat)
    np.testing.assert_allclose(yf, _nhwc_ln(feat, g, b), atol=2e-5)


def test_erf_and_exact_gelu_match_jax():
    x = jnp.linspace(-9.0, 9.0, 20001, dtype=jnp.float32)
    np.testing.assert_allclose(erf_f32(x), jax.lax.erf(x), atol=1e-6)
    exact = jax.nn.gelu(x, approximate=False)
    np.testing.assert_allclose(gelu_erf(x), exact, atol=2e-6)
    np.testing.assert_allclose(
        jax.vmap(jax.grad(gelu_erf))(x),
        jax.vmap(jax.grad(lambda t: jax.nn.gelu(t, approximate=False)))(x),
        atol=1e-6)
    # the tanh form "gelu" stays as it was, and differs past bf16 noise
    assert float(jnp.max(jnp.abs(apply_activation(x, "gelu") - exact))) > 4e-4


def test_gelu_erf_epilogue_and_its_vjp_through_the_pointwise_kernel():
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(k[0], (2, 4, 4, 16))
    w = jax.random.normal(k[1], (1, 1, 16, 24)) * 0.5
    bias = 0.1 * jax.random.normal(k[2], (24,))

    def plain(x, w, bias):
        z = jnp.einsum("nhwc,cd->nhwd", x, w[0, 0],
                       precision=jax.lax.Precision.HIGHEST) + bias
        return jax.nn.gelu(z, approximate=False)

    def kernel(x, w, bias):
        y = pointwise_conv2d_blocked_pallas(
            L.nhwc_to_blocked(x, 8), L.hwio_to_blocked(w, 8, 8),
            bias.reshape(-1, 8), activation="gelu_erf", interpret=True)
        return L.blocked_to_nhwc(y)

    np.testing.assert_allclose(kernel(x, w, bias), plain(x, w, bias),
                               atol=2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(8), (2, 4, 4, 24))
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), argnums=(0, 1, 2))(
        x, w, bias)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), argnums=(0, 1, 2))(
        x, w, bias)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
