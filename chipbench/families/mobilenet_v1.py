"""A MobileNet-v1 configuration as the program runs it.

Builds the network from the program's public layers (``BlockedConv2D``,
``DepthwiseSeparableBlock``, ``BlockedCNN``) at the configuration's widths
and policy, and lays the reference's HWIO weights out as the program stores
them: ``[Co/Cob, Cig/Cbw, Hf, Wf, Cbw, Cob]`` weights, ``[Co/Cob, Cob]``
biases.  The layout is a permutation, so a leaf's norm is the same in both.
"""
from __future__ import annotations

import jax

from . import mobilenet_v1_ref as ref


def build(cfg):
    from repro.nn.conv import BlockedCNN, BlockedConv2D, DepthwiseSeparableBlock
    pol = cfg["precision"]
    stem = cfg["stem"]
    convs = [BlockedConv2D(ci=cfg["in_channels"], co=stem["co"],
                           hf=stem["kernel"], wf=stem["kernel"],
                           stride=stem["stride"], padding="SAME",
                           activation="relu", precision=pol)]
    convs += [DepthwiseSeparableBlock(ci=ci, co=co, hf=3, wf=3, stride=s,
                                      padding="SAME", activation="relu",
                                      precision=pol)
              for ci, co, s in cfg["blocks"]]
    return BlockedCNN(convs=tuple(convs), n_classes=cfg["n_classes"])


def _leaf_layers(model):
    """The model's leaf convs keyed by the reference's parameter paths."""
    out = {("conv0",): model.convs[0]}
    for i, blk in enumerate(model.convs[1:], start=1):
        out[(f"conv{i}", "dw")] = blk.depthwise
        out[(f"conv{i}", "pw")] = blk.pointwise
    return out


def to_program(cfg, model, w):
    """Reference weights -> the program's parameter tree (jitted)."""
    convs = _leaf_layers(model)

    def conv_params(conv, p):
        lay = conv.layout
        hf, wf, cig, co = p["w"].shape
        blk = p["w"].reshape(hf, wf, cig // lay.cb_weight, lay.cb_weight,
                             co // lay.cb_out, lay.cb_out)
        return {"w": blk.transpose(4, 2, 0, 1, 3, 5),
                "b": p["b"].reshape(co // lay.cb_out, lay.cb_out)}

    def convert(w):
        out = {"head": w["head"]}
        for path, conv in convs.items():
            ref.put(out, path, conv_params(conv, ref.get(w, path)))
        return out
    return jax.jit(convert)(w)


def reference_layout(path, x):
    """A program leaf (host array) in the reference's layout: HWIO weights,
    flat biases; the head is the same in both."""
    if path[-1] == "w":
        cob_n, cig_n, hf, wf, cbw, cob = x.shape
        return x.transpose(2, 3, 1, 4, 0, 5).reshape(
            hf, wf, cig_n * cbw, cob_n * cob)
    if path[-1] == "b":
        return x.reshape(-1)
    return x


def leaf_paths(cfg):
    """Parameter paths shared by the program's tree and the reference's."""
    paths = []
    for path, *_ in ref.layers(cfg):
        paths += [path + ("w",), path + ("b",)]
    return paths + [("head",)]
