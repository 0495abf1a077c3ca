"""ConvNeXt through the program's normal path against its plain reference,
on the CPU at a small size: widths 8/16/24/32 (24 is no power of two, and
its pencil holds all 24 channels), depths 1/1/2/1, 32x32 input, 10 classes,
every other key as in ``convnext_t-224``.

* logits, loss and every leaf's gradient of ``BlockedCNN`` (Pallas kernels
  in interpret mode) against ``convnext_ref`` in f32;
* the family lays every reference leaf out as the program stores it and
  back (``to_program`` / ``reference_layout``);
* a training run of the cell's runner at that size, in f32: the sound run
  meets the cell's limits and the float8 control does not.  The limits
  were read in bf16 at the published widths on the chip; at widths of 8–32
  channels, LayerNorms over 8 channels and batches of 8, bf16 rounding
  alone reads past them, so the small network runs at f32 here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testutil import F8, SEED, make_run
from chipbench import harness, train
from chipbench.families import convnext as fam, convnext_ref as ref
from repro.nn.module import init_tree

CELL = "cnx-t-224.train"
SMALL = dict(input_size=32, dims=[8, 16, 24, 32], depths=[1, 1, 2, 1],
             n_classes=10)


def small_cfg(precision="f32"):
    return dict(harness.load_config("convnext_t-224"), precision=precision,
                **SMALL)


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    w = ref.make_weights(cfg, ref.key_for(SEED))
    model = fam.build(cfg)
    p = fam.to_program(cfg, model, w)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (4, 32, 32, 3))
    y = jax.random.randint(k2, (4,), 0, 10)
    return cfg, w, model, p, x, y


def _program_loss(model, p, x, y):
    logits = model(p, x).astype(jnp.float32)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll), logits


def _shapes(tree):
    return {jax.tree_util.keystr(k): v.shape
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def test_layout_round_trip(setup):
    cfg, w, model, p, _, _ = setup
    assert _shapes(p) == _shapes(jax.eval_shape(
        lambda: init_tree(model.specs(), jax.random.PRNGKey(0))))
    for path in fam.leaf_paths(cfg):
        np.testing.assert_array_equal(
            fam.reference_layout(path, np.asarray(ref.get(p, path))),
            np.asarray(ref.get(w, path)))


def test_logits_loss_and_every_gradient_match_the_reference(setup):
    cfg, w, model, p, x, y = setup
    (loss, logits), g = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, x, y), has_aux=True))(p)
    with jax.default_matmul_precision("highest"):
        want_logits = ref.forward(cfg, w, x)
        want_loss, want_g = jax.value_and_grad(
            lambda w: ref.loss(cfg, w, x, y))(w)
    scale = float(jnp.max(jnp.abs(want_logits)))
    np.testing.assert_allclose(logits, want_logits, atol=1e-5 * scale)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for path in fam.leaf_paths(cfg):
        got = fam.reference_layout(path, np.asarray(ref.get(g, path)))
        exp = np.asarray(ref.get(want_g, path))
        err = np.linalg.norm(got - exp) / np.linalg.norm(exp)
        assert err < 1e-4, (path, err)


def test_train_run_is_correct_and_its_control_is_not():
    t = harness.load_traffic("train-224-b128")
    run = make_run(CELL, 32, dict(t, batch=8, distinct_batches=3,
                                  ref_block=4))
    run.cfg.update(SMALL, precision="f32")
    st = train.Setup(run, SEED)
    errs = train.errors(run, st, (F8,))
    prog, ctrl = errs["program"], errs[str(F8)]
    assert all(prog[k] <= v for k, v in run.limits.items()), prog
    assert any(ctrl[k] > v for k, v in run.limits.items()), ctrl
