"""The main-path kernels compile for a TPU v5e.

Each test lowers one launch at a MobileNet-v1 or ConvNeXt-T width and
compiles it for a
described (not attached) ``v5e:2x2`` topology with the installed TPU
compiler — what Mosaic would refuse on the chip (unaligned windows, strided
slices, too much VMEM) it refuses here, at no chip time.  Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the suite runs under
several workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blocking import TPU_V5E
from repro.kernels.conv2d_depthwise import depthwise_conv2d_blocked_pallas
from repro.kernels.conv2d_pointwise import pointwise_conv2d_blocked_pallas
from repro.kernels.direct_conv2d import (direct_conv2d_blocked_pallas,
                                         direct_conv2d_dgrad_pallas,
                                         direct_conv2d_wgrad_pallas)
from repro.kernels.layer_norm import layer_norm_blocked

DTYPES = ("f32", "bf16")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# every launch compiles, never interprets, against the v5e model
KW = dict(machine=TPU_V5E, interpret=False)


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("stride", [1, 2])
def test_window_forward_compiles(one_chip, stride, precision):
    _compile(one_chip, lambda x, w, b: direct_conv2d_blocked_pallas(
        x, w, b, stride=stride, padding="SAME", activation="relu",
        precision=precision, stream=False, **KW),
        (2, 1, 56, 56, 128), (1, 1, 3, 3, 128, 128), (1, 128))


@pytest.mark.parametrize("precision", DTYPES)
def test_window_dgrad_compiles(one_chip, precision):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    _compile(one_chip, lambda dy, w: direct_conv2d_dgrad_pallas(
        dy.astype(dt), w.astype(dt), stride=1, stream=False, z=dy.astype(dt),
        activation="relu", **KW),
        (2, 1, 30, 30, 128), (1, 1, 3, 3, 128, 128))


@pytest.mark.parametrize("precision", DTYPES)
def test_window_wgrad_compiles(one_chip, precision):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    _compile(one_chip, lambda xp, dy: direct_conv2d_wgrad_pallas(
        xp.astype(dt), dy.astype(dt), 3, 3, stride=2, stream=False,
        out_dtype=jnp.float32, z=dy.astype(dt), activation="relu",
        with_db=True, **KW),
        (2, 1, 57, 57, 128), (2, 1, 28, 28, 128))


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("hw,c,stride", [(112, 32, 1), (112, 64, 2),
                                         (14, 512, 1)])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_depthwise_compiles(one_chip, direction, hw, c, stride, precision):
    cb = min(c, 128)

    def fwd(x, w, b):
        return depthwise_conv2d_blocked_pallas(
            x, w, b, stride=stride, padding="SAME", activation="relu",
            precision=precision, **KW)

    fn = fwd if direction == "fwd" else jax.grad(
        lambda x, w, b: fwd(x, w, b).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    _compile(one_chip, fn, (2, c // cb, hw, hw, cb), (c // cb, 1, 3, 3, 1, cb),
             (c // cb, cb))


@pytest.mark.parametrize("precision", DTYPES)
def test_pointwise_compiles(one_chip, precision):
    def fwd(x, w, b):
        return pointwise_conv2d_blocked_pallas(
            x, w, b, activation="relu", precision=precision, **KW)

    grad = jax.grad(lambda x, w, b: fwd(x, w, b).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    _compile(one_chip, grad, (2, 2, 28, 28, 128), (2, 2, 1, 1, 128, 128),
             (2, 128))


@pytest.mark.parametrize("precision", DTYPES)
def test_stream_forward_compiles(one_chip, precision):
    # the ring DMAs whole rows of the padded input (30 + 2 halo columns: a
    # whole number of packed bf16 tiles — see DESIGN.md §11 on widths)
    _compile(one_chip, lambda x, w, b: direct_conv2d_blocked_pallas(
        x, w, b, stride=1, padding="SAME", activation="relu",
        precision=precision, stream=True, **KW),
        (2, 1, 30, 30, 128), (1, 1, 3, 3, 128, 128), (1, 128))


# ConvNeXt-T: the channel LayerNorm (96-channel pencils padded for the
# launch, and 3 pencils of 128), the 7x7 depthwise conv, and the kernel ==
# stride convs of the stem and the downsampling layers with their dgrad


@pytest.mark.parametrize("k,hw,cb", [(1, 56, 96), (2, 28, 96), (3, 14, 128)])
def test_layer_norm_compiles(one_chip, k, hw, cb):
    def loss(x, g, b):
        y = layer_norm_blocked(x, g, b, precision="bf16", **KW)
        return y.astype(jnp.float32).sum()

    text = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                    (2, k, hw, hw, cb), (k, cb), (k, cb))
    assert "layer_norm_bwd_pallas" in text


def test_depthwise_7x7_compiles(one_chip):
    def fwd(x, w, b):
        return depthwise_conv2d_blocked_pallas(
            x, w, b, stride=1, padding="SAME", activation=None,
            precision="bf16", **KW)

    _compile(one_chip, jax.grad(
        lambda x, w, b: fwd(x, w, b).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), (2, 1, 56, 56, 96), (1, 1, 7, 7, 1, 96), (1, 96))


@pytest.mark.parametrize("k,hw,ci,co", [(4, 224, 3, 96), (2, 56, 96, 96)])
def test_kernel_equals_stride_conv_compiles(one_chip, k, hw, ci, co):
    def fwd(x, w, b):
        return direct_conv2d_blocked_pallas(
            x, w, b, stride=k, padding="VALID", activation=None,
            precision="bf16", stream=False, **KW)

    _compile(one_chip, jax.grad(
        lambda x, w, b: fwd(x, w, b).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), (2, 1, hw, hw, ci), (1, 1, k, k, ci, co), (1, co))
