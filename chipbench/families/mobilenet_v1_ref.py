"""Plain float32 reference of a MobileNet-v1 network, and its weights.

Howard et al. 2017, arXiv:1704.04861: a 3x3 stem convolution, depthwise-
separable blocks (3x3 depthwise + 1x1 pointwise, each followed by bias and
ReLU), a global average pool and a linear classifier.  The layer list comes
from a configuration file (``chipbench/configs/<name>.json``).

This module imports nothing of the program under test.  It makes the
weights (in HWIO, from the seed) that both the program and the reference
run, and it computes the network with ``lax.conv_general_dilated`` at
``precision=HIGHEST``.  ``operand`` names a lower operand dtype for the
control: every conv and matmul operand is rounded to it and the product
accumulated in float32.  For ``float8_e4m3fn`` the gradient that reaches a
rounded operand is rounded to ``float8_e5m2`` after scaling its largest
magnitude to 2**12, as FP8 training does (e4m3 forward, e5m2 backward,
per-tensor scaling); unscaled, most cotangents would flush to zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def layers(cfg):
    """-> [(path, ci, co, k, stride, groups)] in network order."""
    out = [(("conv0",), cfg["in_channels"], cfg["stem"]["co"],
            cfg["stem"]["kernel"], cfg["stem"]["stride"], 1)]
    for i, (ci, co, s) in enumerate(cfg["blocks"], start=1):
        out.append(((f"conv{i}", "dw"), ci, ci, 3, s, ci))
        out.append(((f"conv{i}", "pw"), ci, co, 1, 1, 1))
    return out


def key_for(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def make_weights(cfg, key):
    """He-scaled conv weights ``[k, k, ci/groups, co]``, small biases and a
    fan-in-scaled head ``[C, n_classes]``, all float32."""
    w = {}
    for i, (path, ci, co, k, _, groups) in enumerate(layers(cfg)):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = k * k * (ci // groups)
        put(w, path + ("w",), jax.random.normal(
            kw, (k, k, ci // groups, co)) * (2.0 / fan_in) ** 0.5)
        put(w, path + ("b",), 0.01 * jax.random.normal(kb, (co,)))
    c_last = cfg["blocks"][-1][1]
    w["head"] = jax.random.normal(
        jax.random.fold_in(key, 10 ** 6),
        (c_last, cfg["n_classes"])) / c_last ** 0.5
    return w


GRADIENT_DTYPE = {jnp.dtype("float8_e4m3fn"): jnp.dtype("float8_e5m2")}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, operand):
    return x.astype(operand).astype(jnp.float32)


def _rounded_fwd(x, operand):
    return _rounded(x, operand), None


def _rounded_bwd(operand, _, ct):
    dt = GRADIENT_DTYPE.get(jnp.dtype(operand))
    if dt is None:
        return (ct,)
    scale = jnp.maximum(jnp.max(jnp.abs(ct)), 1e-30) / 2.0 ** 12
    return ((ct / scale).astype(dt).astype(jnp.float32) * scale,)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


def _round(x, operand):
    return x if operand is None else _rounded(x, jnp.dtype(operand))


def forward(cfg, w, x, operand=None):
    """Logits ``[N, n_classes]`` of NHWC images ``x``, in float32."""
    h = x.astype(jnp.float32)
    for path, _, _, _, stride, groups in layers(cfg):
        p = get(w, path)
        h = jax.lax.conv_general_dilated(
            _round(h, operand), _round(p["w"], operand), (stride, stride),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=HIGHEST)
        h = jax.nn.relu(h + p["b"])
    feat = h.mean(axis=(1, 2))
    return jnp.dot(_round(feat, operand), _round(w["head"], operand),
                   precision=HIGHEST)


def loss(cfg, w, images, targets, operand=None):
    """Mean softmax cross entropy over the batch."""
    logits = forward(cfg, w, images, operand)
    ll = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)


def lr_at(opt, t):
    """Linear warm-up to ``lr``: the rate of step ``t`` (1-based)."""
    return opt["lr"] * jnp.minimum(t, opt["warmup_steps"]) / opt["warmup_steps"]


def adamw(opt, w, g, m, v, t):
    """One AdamW update in float32 (decoupled decay, no clipping)."""
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    lr = lr_at(opt, t)

    def upd(p, m, v):
        delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                       + opt["eps"])
        return p - lr * (delta + opt["weight_decay"] * p)
    return jax.tree.map(upd, w, m, v), m, v
