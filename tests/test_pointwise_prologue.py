"""The pointwise backward forms ``dz = g * act'(z)`` once (DESIGN.md §14).

* the prologue dgrad takes every input-channel block into its grid step:
  its ``dz`` equals ``cotangent_prologue`` bit for bit, and the VJP's
  ``dw``/``db`` equal the two-pass reference (``cotangent_prologue`` in
  jnp, then the prologue-free wgrad) bit for bit, across activation x
  input-channel blocks x pencil; its ``dx`` matches lax;
* each prologue launch records ``kernels.pw_prologue`` while it is traced,
  with one evaluation per cotangent element, in a ConvNeXt-T block, a
  MobileNet-v1 block, and both whole networks at their published widths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mobilenet import mobilenet_v1
from repro.core import dispatch
from repro.core import direct_conv as D
from repro.core import layout as L
from repro.core.precision import BF16
from repro.kernels.conv2d_common import cotangent_prologue
from repro.kernels.conv2d_pointwise import (pointwise_conv2d_blocked_pallas,
                                            pointwise_dgrad_pallas,
                                            pointwise_wgrad_pallas)
from repro.nn.conv import (BlockedCNN, ConvNeXtBlock, DepthwiseSeparableBlock,
                           Downsample, PatchifyStem)
from repro.nn.module import init_tree
from repro.utils import spans

COBLK = 2          # two output-channel blocks: the dgrad reduces over co


def _operands(activation, ciblk, pencil):
    rng = np.random.default_rng(ciblk * 1000 + pencil + len(activation))
    ci = ciblk * pencil
    x = jnp.asarray(rng.normal(size=(2, ciblk, 4, 4, pencil)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(COBLK, ciblk, 1, 1, pencil, pencil))
                    / np.sqrt(ci), jnp.float32)
    b = jnp.asarray(rng.normal(size=(COBLK, pencil)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, COBLK, 4, 4, pencil)), jnp.bfloat16)
    return x, w, b, g


@pytest.mark.parametrize("pencil", [96, 128])
@pytest.mark.parametrize("ciblk", [1, 2, 3, 6])
@pytest.mark.parametrize("activation", ["relu", "gelu", "gelu_erf"])
def test_prologue_dgrad_forms_dz_once(activation, ciblk, pencil):
    x, w, b, g = _operands(activation, ciblk, pencil)
    xq, wq = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)

    def conv(x_, w_, b_):
        return pointwise_conv2d_blocked_pallas(
            x_, w_, b_, activation=activation, precision="bf16",
            interpret=True)

    _, vjp = jax.vjp(conv, x, w, b)
    dx, dw, db = vjp(g)

    # the saved pre-activation, as the forward stores it
    z = pointwise_conv2d_blocked_pallas(
        x, w, b, precision="bf16", interpret=True
    ).astype(BF16.residual_dtype).astype(g.dtype)
    dz_ref = jax.jit(cotangent_prologue, static_argnums=2)(g, z, activation)
    dx_k, dz = pointwise_dgrad_pallas(g, wq, interpret=True, z=z,
                                      activation=activation)
    assert dz.dtype == g.dtype
    np.testing.assert_array_equal(np.asarray(dz), np.asarray(dz_ref))
    np.testing.assert_array_equal(np.asarray(dx_k), np.asarray(dx))

    dw_ref, db_ref = pointwise_wgrad_pallas(
        xq, dz_ref, interpret=True, out_dtype=jnp.float32, with_db=True)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_ref))
    np.testing.assert_array_equal(np.asarray(db),
                                  np.asarray(db_ref.astype(b.dtype)))

    # dx against lax on the operands the kernel sees: bf16-rounded x and w
    ci = ciblk * pencil
    wn = L.blocked_to_hwio(wq.astype(jnp.float32))

    def oracle(xn):
        y = jax.lax.conv_general_dilated(
            xn, wn, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        return D.apply_activation(y + b.reshape(-1), activation)

    xn = L.blocked_to_nhwc(xq.astype(jnp.float32))
    _, ovjp = jax.vjp(oracle, xn)
    (want,) = ovjp(L.blocked_to_nhwc(g.astype(jnp.float32)))
    assert want.shape[-1] == ci
    np.testing.assert_allclose(
        np.asarray(L.blocked_to_nhwc(dx.astype(jnp.float32))),
        np.asarray(want), rtol=0.1, atol=0.15)


def _prologue_records(fn, *args):
    """Trace ``fn`` afresh; -> the ``kernels.pw_prologue`` records it
    wrote (attrs).  Jitted launches are traced once per shape, so the
    caches are cleared first."""
    jax.clear_caches()
    spans.clear()
    jax.eval_shape(fn, *args)
    return [r[5] for r in spans.spans() if r[0] == "kernels.pw_prologue"]


@pytest.fixture
def pallas_on_cpu(monkeypatch):
    """Route dense forwards to the Pallas families on the CPU too, as the
    chip does (the prior otherwise prefers the jnp oracle off the chip)."""
    monkeypatch.setattr(dispatch, "_pallas_costly", lambda: False)


def _grad_records(layer, x_shape, seed=0):
    p = init_tree(layer.specs(), jax.random.PRNGKey(seed))
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    return _prologue_records(jax.grad(
        lambda p_, x_: jnp.sum(layer(p_, x_).astype(jnp.float32))), p, x)


@pytest.mark.parametrize("block", ["convnext", "mobilenet"])
def test_block_gradient_records_one_evaluation(pallas_on_cpu, block):
    if block == "convnext":
        layer = ConvNeXtBlock(c=16, lane=8, precision="bf16")
        want_ci_blocks = 2                       # pw1: 16 channels, 8 lanes
    else:
        layer = DepthwiseSeparableBlock(ci=24, co=32, hf=3, wf=3, stride=1,
                                        padding="SAME", activation="relu",
                                        lane=8, precision="bf16")
        want_ci_blocks = 3                       # pw: 24 channels, 8 lanes
    recs = _grad_records(layer, (2, layer.ci // 8, 8, 8, 8))
    assert recs == [{"direction": "dgrad", "ci_blocks": want_ci_blocks,
                     "rows": 64, "evaluations": 1}]


def _convnext_t():
    dims, depths = (96, 192, 384, 768), (3, 3, 9, 3)
    kw = dict(precision="bf16")
    layers = [PatchifyStem(ci=3, co=dims[0], k=4, **kw)]
    for i, (c, d) in enumerate(zip(dims, depths)):
        if i:
            layers.append(Downsample(ci=dims[i - 1], co=c, k=2, **kw))
        layers += [ConvNeXtBlock(c=c, **kw) for _ in range(d)]
    return BlockedCNN(convs=tuple(layers), n_classes=1000)


@pytest.mark.parametrize("net,size,want", [
    # one record per distinct traced launch: a stage's identical blocks
    # share one trace (pw1 over 96/192/384/768 channels in 96/96/128/128
    # lanes); MobileNet's 13 pointwise convs trace as 9 shapes
    ("convnext_t", 64, [1, 2, 3, 6]),
    ("mobilenet_v1", 64, [1, 1, 1, 1, 2, 2, 4, 4, 8]),
])
def test_network_gradient_records_one_evaluation(pallas_on_cpu, net, size,
                                                 want):
    model = _convnext_t() if net == "convnext_t" else mobilenet_v1("bf16")
    p = jax.eval_shape(lambda k: init_tree(model.specs(), k),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32)
    recs = _prologue_records(jax.grad(
        lambda p_, x_: jnp.sum(model(p_, x_).astype(jnp.float32))), p, x)
    assert sorted(r["ci_blocks"] for r in recs) == want
    assert all(r["evaluations"] == 1 and r["direction"] == "dgrad"
               for r in recs)
