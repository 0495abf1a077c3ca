"""Plain float32 reference of a ConvNeXt network, and its weights.

Liu et al. 2022, "A ConvNet for the 2020s" (arXiv:2201.03545), Section 2
and Fig. 4; the reference code is ``models/convnext.py`` in
github.com/facebookresearch/ConvNeXt.  A 4x4 stride-4 patchify stem and a
LayerNorm; four stages of ConvNeXt blocks (7x7 depthwise conv, LayerNorm,
a 4x inverted bottleneck of two linear layers with an exact GELU between,
a per-channel layer scale, an identity skip), a LayerNorm and a 2x2
stride-2 conv between stages; a global average pool, a LayerNorm and a
linear classifier.  Depths and widths come from a configuration file
(``chipbench/configs/<name>.json``).

This module imports nothing of the program under test.  It makes the
weights (convs in HWIO, from the seed) that both the program and the
reference run, and it computes the network in NHWC with
``lax.conv_general_dilated`` at ``precision=HIGHEST`` and LayerNorm as the
paper writes it.  ``operand`` names a lower operand dtype for the control,
rounding every conv and matmul operand as ``mobilenet_v1_ref`` does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mobilenet_v1_ref import (_round, adamw, get, key_for,  # noqa: F401
                               lr_at, put)

HIGHEST = jax.lax.Precision.HIGHEST


def plan(cfg):
    """-> [(name, kind, ci, co)] in network order; ``kind`` is ``stem``,
    ``block`` (``ci == co``) or ``down``."""
    dims, out = cfg["dims"], [("conv0", "stem", cfg["in_channels"],
                               cfg["dims"][0])]
    for s, (depth, c) in enumerate(zip(cfg["depths"], dims)):
        if s:
            out.append((f"conv{len(out)}", "down", dims[s - 1], c))
        out += [(f"conv{len(out) + i}", "block", c, c) for i in range(depth)]
    return out


def layers(cfg):
    """-> [(path, ci, co, k, stride, groups)] of every conv and linear
    layer that carries a ``w`` and a ``b``, in network order."""
    cfk, s, e = cfg["stem"]["kernel"], cfg["down_kernel"], cfg["expansion"]
    dwk = cfg["dw_kernel"]
    out = []
    for name, kind, ci, co in plan(cfg):
        if kind == "stem":
            out.append(((name, "conv"), ci, co, cfk, cfk, 1))
        elif kind == "down":
            out.append(((name, "conv"), ci, co, s, s, 1))
        else:
            out += [((name, "dw"), ci, ci, dwk, 1, ci),
                    ((name, "pw1"), ci, e * ci, 1, 1, 1),
                    ((name, "pw2"), e * ci, ci, 1, 1, 1)]
    return out


def norms(cfg):
    """-> [(path, channels)] of every LayerNorm, in network order."""
    out = []
    for name, kind, ci, co in plan(cfg):
        out.append(((name, "norm"), co if kind == "stem" else ci))
    return out + [(("head_norm",), cfg["dims"][-1])]


def make_weights(cfg, key):
    """He-scaled conv and linear weights ``[k, k, ci/groups, co]``, biases
    of scale 0.01, LayerNorm scales 1 + 0.05 N(0, 1) and shifts 0.05 N(0,
    1), layer scales uniform in [0.1, 1] and a fan-in-scaled classifier
    ``[C, n_classes]`` with a bias of scale 0.01, all float32."""
    w = {}
    for i, (path, ci, co, k, _, groups) in enumerate(layers(cfg)):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = k * k * (ci // groups)
        put(w, path + ("w",), jax.random.normal(
            kw, (k, k, ci // groups, co)) * (2.0 / fan_in) ** 0.5)
        put(w, path + ("b",), 0.01 * jax.random.normal(kb, (co,)))
    for i, (path, c) in enumerate(norms(cfg)):
        kg, kb = jax.random.split(jax.random.fold_in(key, 10 ** 5 + i))
        put(w, path + ("g",), 1.0 + 0.05 * jax.random.normal(kg, (c,)))
        put(w, path + ("b",), 0.05 * jax.random.normal(kb, (c,)))
    for i, (name, kind, c, _) in enumerate(plan(cfg)):
        if kind == "block":
            w[name]["gamma"] = jax.random.uniform(
                jax.random.fold_in(key, 2 * 10 ** 5 + i), (c,),
                minval=0.1, maxval=1.0)
    c_last = cfg["dims"][-1]
    kh, kb = jax.random.split(jax.random.fold_in(key, 10 ** 6))
    w["head"] = jax.random.normal(kh, (c_last, cfg["n_classes"])) / c_last ** 0.5
    w["head_bias"] = 0.01 * jax.random.normal(kb, (cfg["n_classes"],))
    return w


def leaves(cfg):
    """Every parameter path, in network order."""
    out = [p + (leaf,) for p, *_ in layers(cfg) for leaf in ("w", "b")]
    out += [p + (leaf,) for p, _ in norms(cfg) for leaf in ("g", "b")]
    out += [(name, "gamma") for name, kind, *_ in plan(cfg)
            if kind == "block"]
    return out + [("head",), ("head_bias",)]


def layer_norm(x, p, eps):
    """Over the last (channel) axis: biased variance, eps in the root."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _conv(h, p, stride, padding, groups, operand):
    return jax.lax.conv_general_dilated(
        _round(h, operand), _round(p["w"], operand), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST) + p["b"]


def _linear(h, p, operand):
    return jnp.einsum("nhwc,cd->nhwd", _round(h, operand),
                      _round(p["w"][0, 0], operand),
                      precision=HIGHEST) + p["b"]


def forward(cfg, w, x, operand=None):
    """Logits ``[N, n_classes]`` of NHWC images ``x``, in float32."""
    eps, h = cfg["ln_eps"], x.astype(jnp.float32)
    cfk, s = cfg["stem"]["kernel"], cfg["down_kernel"]
    for name, kind, ci, _ in plan(cfg):
        p = w[name]
        if kind == "stem":
            h = layer_norm(_conv(h, p["conv"], cfk, "VALID", 1, operand),
                           p["norm"], eps)
        elif kind == "down":
            h = _conv(layer_norm(h, p["norm"], eps), p["conv"], s, "VALID",
                      1, operand)
        else:
            y = _conv(h, p["dw"], 1, "SAME", ci, operand)
            y = layer_norm(y, p["norm"], eps)
            y = jax.nn.gelu(_linear(y, p["pw1"], operand), approximate=False)
            h = h + p["gamma"] * _linear(y, p["pw2"], operand)
    feat = layer_norm(h.mean(axis=(1, 2)), w["head_norm"], eps)
    return jnp.dot(_round(feat, operand), _round(w["head"], operand),
                   precision=HIGHEST) + w["head_bias"]


def loss(cfg, w, images, targets, operand=None):
    """Mean softmax cross entropy over the batch."""
    logits = forward(cfg, w, images, operand)
    ll = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)
