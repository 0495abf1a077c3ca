"""A ConvNeXt configuration as the program runs it.

Builds the network from the program's public layers (``PatchifyStem``,
``ConvNeXtBlock``, ``Downsample``, ``BlockedLayerNorm``, ``BlockedCNN``) at
the configuration's depths, widths and policy, and lays the reference's
weights out as the program stores them: ``[Co/Cob, Cig/Cbw, Hf, Wf, Cbw,
Cob]`` conv weights, ``[C/Cb, Cb]`` pencils for every per-channel vector
(biases, LayerNorm scales and shifts, layer scales); the classifier and its
bias are the same in both.  The layout is a permutation, so a leaf's norm
is the same in both.
"""
from __future__ import annotations

import jax

from . import convnext_ref as ref


def build(cfg):
    from repro.nn.conv import (BlockedCNN, BlockedLayerNorm, ConvNeXtBlock,
                               Downsample, PatchifyStem)
    kw = dict(eps=cfg["ln_eps"], precision=cfg["precision"])
    layers = []
    for _, kind, ci, co in ref.plan(cfg):
        if kind == "stem":
            layers.append(PatchifyStem(ci=ci, co=co, k=cfg["stem"]["kernel"],
                                       **kw))
        elif kind == "down":
            layers.append(Downsample(ci=ci, co=co, k=cfg["down_kernel"], **kw))
        else:
            layers.append(ConvNeXtBlock(c=ci, k=cfg["dw_kernel"],
                                        expansion=cfg["expansion"], **kw))
    return BlockedCNN(convs=tuple(layers), n_classes=cfg["n_classes"],
                      head_norm=BlockedLayerNorm(c=cfg["dims"][-1], **kw),
                      head_bias=True)


def _leaf_layers(model):
    """The model's layers keyed by the reference's parameter paths: each
    conv, each LayerNorm, and for a layer scale the conv it is folded
    into."""
    out = {("head_norm",): model.head_norm}
    for i, layer in enumerate(model.convs):
        name = f"conv{i}"
        out[(name, "norm")] = layer.norm
        if hasattr(layer, "depthwise"):
            out.update({(name, "dw"): layer.depthwise,
                        (name, "pw1"): layer.pw1, (name, "pw2"): layer.pw2,
                        (name, "gamma"): layer.pw2})
        else:
            out[(name, "conv")] = layer.conv
    return out


def to_program(cfg, model, w):
    """Reference weights -> the program's parameter tree (jitted)."""
    owners = _leaf_layers(model)

    def convert(w):
        out = {"head": w["head"], "head_bias": w["head_bias"]}
        for path in ref.leaves(cfg):
            if len(path) == 1:               # the classifier, as it is
                continue
            x = ref.get(w, path)
            owner = owners[path[:-1] if path[-1] in ("w", "b", "g")
                           else path]
            if x.ndim == 4:                  # HWIO conv weight
                lay = owner.layout
                hf, wf, cig, co = x.shape
                x = x.reshape(hf, wf, cig // lay.cb_weight, lay.cb_weight,
                              co // lay.cb_out, lay.cb_out
                              ).transpose(4, 2, 0, 1, 3, 5)
            else:                            # per-channel vector
                x = x.reshape(-1, owner.out_pencil)
            ref.put(out, path, x)
        return out
    return jax.jit(convert)(w)


def reference_layout(path, x):
    """A program leaf (host array) in the reference's layout: HWIO conv
    weights, flat per-channel vectors; the classifier and its bias are the
    same in both."""
    if path in (("head",), ("head_bias",)):
        return x
    if x.ndim == 6:
        cob_n, cig_n, hf, wf, cbw, cob = x.shape
        return x.transpose(2, 3, 1, 4, 0, 5).reshape(
            hf, wf, cig_n * cbw, cob_n * cob)
    return x.reshape(-1)


def leaf_paths(cfg):
    """Parameter paths shared by the program's tree and the reference's."""
    return ref.leaves(cfg)
