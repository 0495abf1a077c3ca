"""Per-kernel Pallas (interpret-mode) vs pure-jnp oracle, swept over shapes
and dtypes — the required kernel validation."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import layout as L
from repro.core.context import ConvContext
from repro.core.conv_baselines import conv_lax
from repro.kernels import ops, ref
from repro.kernels.direct_conv2d import direct_conv2d_blocked_pallas

CONV2D_CASES = [
    # hi, wi, ci, co, hf, wf, stride
    (10, 11, 8, 16, 3, 3, 1),
    (12, 12, 4, 8, 5, 5, 2),
    (8, 8, 3, 6, 1, 1, 1),
    (9, 9, 2, 4, 2, 2, 1),
    (14, 10, 6, 12, 3, 5, 2),
    (7, 7, 16, 32, 3, 3, 1),
]


@pytest.mark.parametrize("case", CONV2D_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_direct_conv2d_pallas_vs_oracle(case, dtype):
    hi, wi, ci, co, hf, wf, stride = case
    rng = np.random.default_rng(hash(case) % 2**32)
    x = jnp.asarray(rng.normal(size=(2, hi, wi, ci)), dtype)
    w = jnp.asarray(rng.normal(size=(hf, wf, ci, co)), dtype)
    got = ops.direct_conv2d(
        x, w, stride=stride,
        context=ConvContext(impl="window", interpret=True))
    want = conv_lax(x.astype(jnp.float32), w.astype(jnp.float32), stride)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_direct_conv2d_blocked_ref_matches():
    """The blocked-layout ref oracle itself is consistent with lax.conv."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 9, 9, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    lay = L.BlockedConvLayout.choose(4, 8)
    xb = L.nhwc_to_blocked(x, lay.cb_in)
    wb = L.hwio_to_blocked(w, lay.cb_in, lay.cb_out)
    got = direct_conv2d_blocked_pallas(xb, wb, stride=1, interpret=True)
    want = ref.direct_conv2d_ref(xb, wb, stride=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


CONV1D_CASES = [
    # L, D, K, lb
    (16, 256, 4, 8),
    (32, 128, 4, 32),
    (24, 64, 3, 8),
    (8, 32, 2, 4),
    (64, 512, 4, 16),
]


@pytest.mark.parametrize("case", CONV1D_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_depthwise_pallas_vs_oracle(case, dtype):
    l, d, k, lb = case
    rng = np.random.default_rng(hash(case) % 2**32)
    x = jnp.asarray(rng.normal(size=(2, l, d)), dtype)
    w = jnp.asarray(rng.normal(size=(k, d)), dtype)
    got = ops.conv1d_depthwise(x, w, lb=lb, interpret=True)
    want = ref.conv1d_depthwise_ref(x.astype(jnp.float32),
                                    w.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_conv1d_cross_block_causality():
    """The two-BlockSpec causal-tail trick: results identical across lb."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 32, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
    outs = [np.asarray(ops.conv1d_depthwise(x, w, lb=lb, interpret=True))
            for lb in (4, 8, 16, 32)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


def test_conv1d_bias():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 8, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    got = ops.conv1d_depthwise(x, w, bias=b, interpret=True)
    want = ref.conv1d_depthwise_ref(x, w, bias=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_grid_reduction_order():
    """Accumulation over Ci blocks (innermost grid dim) is exact for any
    number of input-channel blocks."""
    rng = np.random.default_rng(5)
    for ci in (4, 8, 16):
        x = jnp.asarray(rng.normal(size=(1, 6, 6, ci)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(3, 3, ci, 8)).astype(np.float32))
        lay = L.BlockedConvLayout.choose(ci, 8, lane=4)   # force multi-block
        xb = L.nhwc_to_blocked(x, lay.cb_in)
        wb = L.hwio_to_blocked(w, lay.cb_in, lay.cb_out)
        got = direct_conv2d_blocked_pallas(xb, wb, interpret=True)
        want = ref.direct_conv2d_ref(xb, wb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["dense-s1", "dense-s2", "depthwise"])
@pytest.mark.parametrize("gap", [False, True])
def test_narrow_pencil_lane_padding_is_exact(monkeypatch, kind, gap):
    """A compiled launch zero-pads narrow pencils to 128 lanes and crops
    the result (``conv2d_common.lane_pad``); interpret mode pads nothing.
    Forcing the pad here (interpret mode) gives the unpadded launch's
    values: zero lanes in, cropped out, grads included."""
    import importlib
    import jax
    from repro.core.blocking import TPU_V5E
    from repro.kernels.conv2d_common import lane_pad
    DC = importlib.import_module("repro.kernels.direct_conv2d")
    DW = importlib.import_module("repro.kernels.conv2d_depthwise")

    assert lane_pad(TPU_V5E, True, 3, 32) == (0, 0)
    assert lane_pad(TPU_V5E, False, 3, 32, 128) == (125, 96, 0)

    rng = np.random.default_rng(7)
    c, stride = (8, 1) if kind == "depthwise" else (3, int(kind[-1]))
    co = 8 if kind == "depthwise" else 4
    x = jnp.asarray(rng.normal(size=(2, 1, 9, 9, c)), jnp.float32)
    if kind == "depthwise":
        w = jnp.asarray(rng.normal(size=(1, 1, 3, 3, 1, c)), jnp.float32)
        fn, mod = DW.depthwise_conv2d_blocked_pallas, DW
    else:
        w = jnp.asarray(rng.normal(size=(1, 1, 3, 3, c, co)), jnp.float32)
        fn, mod = DC.direct_conv2d_blocked_pallas, DC
    b = jnp.asarray(rng.normal(size=(1, co)), jnp.float32)

    def run(x, w, b):
        return fn(x, w, b, stride=stride, padding="SAME", activation="relu",
                  interpret=True, gap=gap)

    def loss(x, w, b):
        return (run(x, w, b) ** 2).sum()

    want = run(x, w, b)
    gwant = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    monkeypatch.setattr(mod, "lane_pad",
                        lambda m, interp, *p: lane_pad(m, False, *p))
    jax.clear_caches()                  # the entry points are jitted
    got = run(x, w, b)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for g, gw in zip(jax.grad(loss, argnums=(0, 1, 2))(x, w, b), gwant):
        assert g.shape == gw.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(gw),
                                   rtol=1e-4, atol=1e-4)
    jax.clear_caches()                  # drop the padded traces
