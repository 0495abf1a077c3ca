"""Depthwise input gradient at the unpadded input's own extents.

* ``jax.grad`` through ``depthwise_conv2d_blocked_pallas`` (interpret mode)
  == the ``conv_lax`` oracle for dx, dw and db, across padding x stride x
  odd/even extent x dilation x activation;
* the dgrad launch that pads the cotangent by the forward's pads, transposed,
  is bit-identical to the full-halo (VALID) dgrad padded and cropped to the
  input afterwards;
* for each of MobileNet-v1 1.0-224's 13 depthwise layers the dgrad launch
  writes the layer's input extent, tiled at least 8 rows deep (pure Python,
  at the training cell's bf16 / 128-lane / fused-prologue arguments).
"""
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.mobilenet import BLOCKS, INPUT_SIZE, STEM
from repro.core import layout as L
from repro.core.blocking import (TPU_V5E, choose_depthwise_blocking,
                                 dgrad_extents)
from repro.core.conv_baselines import conv_lax
from repro.core.direct_conv import apply_activation
from repro.core.padding import normalize_padding
from repro.kernels.conv2d_depthwise import (depthwise_conv2d_blocked_pallas,
                                            depthwise_dgrad_pallas,
                                            dgrad_pads)

C, LANE = 8, 8


def _operands(key, hw, c=C):
    rng = np.random.default_rng(zlib.crc32(repr(key).encode()))
    x = jnp.asarray(rng.normal(size=(2, hw, hw, c)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 1, c)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(c,)).astype(np.float32))
    return rng, x, w, b


@pytest.mark.parametrize("activation", ["relu", None])
@pytest.mark.parametrize("dilation", [1, 2])
@pytest.mark.parametrize("hw", [16, 15])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_depthwise_vjp_matches_lax(padding, stride, hw, dilation,
                                   activation):
    key = (padding, stride, hw, dilation, activation)
    rng, x, w, b = _operands(key, hw)

    def ref(x_, w_, b_):
        y = conv_lax(x_, w_, stride, padding, groups=C, dilation=dilation)
        return apply_activation(y + b_, activation)

    y = ref(x, w, b)
    cot = jnp.asarray(rng.normal(size=y.shape).astype(np.float32))
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * cot),
                    argnums=(0, 1, 2))(x, w, b)

    cotb = L.nhwc_to_blocked(cot, LANE)

    def pallas(xb_, wb_, bb_):
        out = depthwise_conv2d_blocked_pallas(
            xb_, wb_, bb_, stride=stride, padding=padding,
            activation=activation, interpret=True, dilation=dilation)
        return jnp.sum(out * cotb)

    dx, dw, db = jax.grad(pallas, argnums=(0, 1, 2))(
        L.nhwc_to_blocked(x, LANE), L.hwio_to_blocked(w, 1, LANE),
        b.reshape(C // LANE, LANE))
    assert dx.shape == (2, C // LANE, hw, hw, LANE)
    for got, exp in ((dx, L.nhwc_to_blocked(want[0], LANE)),
                     (dw, L.hwio_to_blocked(want[1], 1, LANE)),
                     (db, want[2].reshape(C // LANE, LANE))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("padding,stride,hw,dilation", [
    ("SAME", 1, 16, 1),
    ("SAME", 2, 16, 1),
    ("SAME", 2, 15, 2),
    ("VALID", 2, 16, 1),       # a trailing input row the taps never touch
    (((3, 0), (0, 3)), 1, 15, 1),   # pads beyond the filter reach: crops
])
def test_dgrad_at_input_extents_bitwise_equals_pad_then_crop(
        padding, stride, hw, dilation, dtype):
    """The new launch == the full-halo dgrad of the padded input, padded
    with zeros to the padded extents and cropped to the input, bit for bit
    (fused relu prologue included)."""
    key = (padding, stride, hw, dilation, str(dtype))
    rng, _, w, _ = _operands(key, hw, c=2 * LANE)
    pads = normalize_padding(padding, (3 - 1) * dilation + 1,
                             (3 - 1) * dilation + 1, stride, hw, hw)
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    ho = (hw + ph_lo + ph_hi - 2 * dilation - 1) // stride + 1
    wo = (hw + pw_lo + pw_hi - 2 * dilation - 1) // stride + 1
    g, z = (jnp.asarray(rng.normal(size=(2, 2, ho, wo, LANE)), dtype)
            for _ in range(2))
    wb = L.hwio_to_blocked(w, 1, LANE).astype(dtype)
    kw = dict(stride=stride, dilation=(dilation, dilation), z=z,
              activation="relu", interpret=True)

    eh, ew = dgrad_extents(ho, wo, 3, 3, stride, (dilation, dilation))
    full = depthwise_dgrad_pallas(g, wb, (eh, ew), **kw)
    hp, wp = hw + ph_lo + ph_hi, hw + pw_lo + pw_hi
    full = jnp.pad(full, ((0, 0), (0, 0), (0, hp - eh), (0, wp - ew),
                          (0, 0)))
    old = full[:, :, ph_lo:ph_lo + hw, pw_lo:pw_lo + hw, :]

    new = depthwise_dgrad_pallas(g, wb, (hw, hw), pads=pads, **kw)
    assert new.shape == old.shape == (2, 2, hw, hw, LANE)
    assert new.dtype == old.dtype
    np.testing.assert_array_equal(np.asarray(new.astype(jnp.float32)),
                                  np.asarray(old.astype(jnp.float32)))


def _mnv1_depthwise_layers():
    """(name, input extent, channels, stride) of MobileNet-v1 1.0-224's 13
    depthwise layers."""
    h = -(-INPUT_SIZE // STEM[2])
    layers = []
    for i, (ci, _, s) in enumerate(BLOCKS, start=1):
        layers.append((f"conv{i}.dw", h, ci, s))
        h = -(-h // s)
    return layers


@pytest.mark.parametrize("layer", _mnv1_depthwise_layers(),
                         ids=lambda layer: layer[0])
def test_mnv1_depthwise_dgrad_tiles_at_input_extent(layer):
    _, hi, ci, stride = layer
    cb = 128
    c = -(-ci // cb) * cb                     # lanes padded to whole pencils
    (ph_lo, ph_hi), _ = normalize_padding("SAME", 3, 3, stride, hi, hi)
    ho = (hi + ph_lo + ph_hi - 3) // stride + 1
    lo, hi_pad = dgrad_pads(ho, hi, 3, stride, 1, ph_lo)
    extent = lo + (ho - 1) * stride + 1 + hi_pad       # padded cotangent
    assert extent - 2 == hi                  # the launch's output extent
    blk = choose_depthwise_blocking(extent, extent, c, 3, 3, 1,
                                    machine=TPU_V5E, cb=cb, in_dtype_bytes=2,
                                    fused_prologue=True)
    assert hi % blk.hob == 0 and hi % blk.wob == 0
    assert blk.hob >= min(8, hi)
