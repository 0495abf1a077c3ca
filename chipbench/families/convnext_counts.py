"""The work of each conv and LayerNorm of a ConvNeXt configuration, at its
published widths.

Convs are ``counts.Conv`` and their least times come from
``counts.least_seconds``, so the roofline arithmetic is the one the
MobileNet readers use: channels are never rounded up to the lanes a kernel
pads them to.  ``family`` is ``stem`` (the patchify conv, which needs no
input gradient), ``depthwise``, ``pointwise`` (the two linear layers of
each block) or ``window`` (the downsampling convs).

Mult-adds and parameters are the published network's, the numbers the
paper tabulates: every conv and the classifier multiply-add; LayerNorm,
GELU, the layer scale and the biases are not counted as mult-adds; every
weight, bias, LayerNorm scale and shift and layer scale is a parameter.

A LayerNorm's least HBM bytes: forward, read x and write y at the operand
dtype plus its f32 scale and shift; backward, read x and dy and write dx at
the operand dtype plus the f32 gradients of scale and shift.  It is bound
by memory.  Those of ``norms(cfg)`` are the 22 inside the network (1 after
the stem, one in each block, one before each downsampling conv) and the
head's, which runs on the pooled features as a map of one pixel.
"""
from __future__ import annotations

import dataclasses

from chipbench.counts import ITEMSIZE, Conv, directions, least_seconds


@dataclasses.dataclass(frozen=True)
class Norm:
    name: str
    c: int
    hw: int              # pixels of one image

    def bytes_moved(self, n: int, direction: str, precision: str) -> int:
        e = ITEMSIZE[precision]
        maps = (2 if direction == "fwd" else 3) * n * self.hw * self.c
        return e * maps + 4 * 2 * self.c


def convs(cfg):
    """The configuration's convs in network order, with their extents."""
    h = cfg["input_size"]
    out = []

    def add(name, family, ci, co, k, stride, groups, gap=False):
        nonlocal h
        ho = h // stride
        out.append(Conv(name, family, ci, co, k, stride, groups, h, ho, gap))
        h = ho
    k0 = cfg["stem"]["kernel"]
    add("stem", "stem", cfg["in_channels"], cfg["dims"][0], k0, k0, 1)
    kd, kdw, e = cfg["down_kernel"], cfg["dw_kernel"], cfg["expansion"]
    last = len(cfg["depths"]) - 1
    for s, (depth, c) in enumerate(zip(cfg["depths"], cfg["dims"])):
        if s:
            add(f"down{s}", "window", cfg["dims"][s - 1], c, kd, kd, 1)
        for b in range(depth):
            name = f"s{s}b{b}"
            add(f"{name}.dw", "depthwise", c, c, kdw, 1, c)
            add(f"{name}.pw1", "pointwise", c, e * c, 1, 1, 1)
            add(f"{name}.pw2", "pointwise", e * c, c, 1, 1, 1,
                gap=(s == last and b == depth - 1))
    return out


def norms(cfg):
    """Every LayerNorm with the pixels it normalises, in network order."""
    cs = convs(cfg)
    out = [Norm("stem.norm", cs[0].co, cs[0].ho ** 2)]
    for c in cs[1:]:
        if c.family == "window":
            out.append(Norm(f"{c.name}.norm", c.ci, c.hi ** 2))
        elif c.name.endswith(".dw"):
            out.append(Norm(f"{c.name[:-3]}.norm", c.ci, c.ho ** 2))
    return out + [Norm("head.norm", cfg["dims"][-1], 1)]


def head_macs(cfg) -> int:
    return cfg["dims"][-1] * cfg["n_classes"]


def mult_adds(cfg) -> int:
    """Multiply-adds of one image's forward pass (convs and classifier)."""
    return sum(c.macs_per_image for c in convs(cfg)) + head_macs(cfg)


def params(cfg) -> int:
    """Weights and biases of convs and classifier, LayerNorm scales and
    shifts, layer scales."""
    return (sum(c.weights + c.co for c in convs(cfg))
            + head_macs(cfg) + cfg["n_classes"]
            + sum(2 * n.c for n in norms(cfg))
            + sum(d * c for d, c in zip(cfg["depths"], cfg["dims"])))


def train_flops(cfg) -> int:
    """FLOPs of one image's forward and backward pass: every conv's forward
    and weight gradient, every input gradient but the stem's, and the
    classifier's three matmuls.  Nothing recomputed is counted."""
    total = 6 * head_macs(cfg)
    for c in convs(cfg):
        total += 2 * c.macs_per_image * (2 if c.family == "stem" else 3)
    return total


def least_per_step(run, family: str) -> float:
    """Least seconds of one training step's launches of ``family``: a conv
    family's layers in every direction, or with ``"layer_norm"`` every
    LayerNorm forward and backward (bound by memory)."""
    rows = run.traffic["batch"] // run.traffic.get("data", 1)
    prec = run.cfg["precision"]
    if family == "layer_norm":
        return sum(n.bytes_moved(rows, d, prec)
                   for n in norms(run.cfg) for d in ("fwd", "bwd")
                   ) / run.peak["hbm_bytes_per_s"]
    return sum(least_seconds(c, rows, d, prec, run.peak)[0]
               for c in convs(run.cfg) if c.family == family
               for d in directions(c, True))


def roofline_share(run, family: str, seconds) -> float | None:
    """Percent of the roofline reached by launches that took ``seconds`` of
    device time over the window's training steps; None without a trace,
    steps or device time."""
    steps = run.counters.get("steps")
    if run.trace is None or not steps or not seconds:
        return None
    return 100.0 * steps * least_per_step(run, family) / seconds
