"""The work each conv of a configuration needs, at its published widths.

Counts are of the published layer, whatever implements it: channels are
never rounded up to the lanes a kernel pads them to, so a change that packs
narrow pencils reads as a higher share of the roofline, never as more work.
Activations and weights are counted at the configuration's operand dtype,
biases and weight gradients in float32 (the master dtype).

Forward: ``2 * N * Ho * Wo * Co * (Ci / groups) * k * k`` FLOPs; bytes read
are the input map, the weights and the bias, bytes written the output map
(``[N, Co]`` when the global average pool rides the last conv's epilogue).
Backward: the input gradient (dgrad) costs the forward's FLOPs and reads the
output gradient and the weights, writing the input gradient; the weight
gradient (wgrad) costs the forward's FLOPs and reads the input map and the
output gradient, writing the weight and bias gradients.  The stem needs no
input gradient.
"""
from __future__ import annotations

import dataclasses

ITEMSIZE = {"bf16": 2, "f32": 4}


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    family: str          # "stem" | "depthwise" | "pointwise"
    ci: int
    co: int
    k: int
    stride: int
    groups: int
    hi: int
    ho: int
    gap: bool            # the global average pool is fused into this conv

    @property
    def macs_per_image(self) -> int:
        return self.ho * self.ho * self.co * (self.ci // self.groups) * self.k ** 2

    @property
    def weights(self) -> int:
        return self.k * self.k * (self.ci // self.groups) * self.co


def convs(cfg):
    """The configuration's convs in network order, with their extents."""
    h = cfg["input_size"]
    stem = cfg["stem"]
    out = []

    def add(name, family, ci, co, k, stride, groups, gap=False):
        nonlocal h
        ho = -(-h // stride)
        out.append(Conv(name, family, ci, co, k, stride, groups, h, ho, gap))
        h = ho
    add("conv0", "stem", cfg["in_channels"], stem["co"], stem["kernel"],
        stem["stride"], 1)
    last = len(cfg["blocks"])
    for i, (ci, co, s) in enumerate(cfg["blocks"], start=1):
        add(f"conv{i}.dw", "depthwise", ci, ci, 3, s, ci)
        add(f"conv{i}.pw", "pointwise", ci, co, 1, 1, 1, gap=(i == last))
    return out


def head_macs(cfg) -> int:
    return cfg["blocks"][-1][1] * cfg["n_classes"]


def mult_adds(cfg) -> int:
    """Multiply-adds of one image's forward pass (convs and classifier),
    the number the paper tabulates."""
    return sum(c.macs_per_image for c in convs(cfg)) + head_macs(cfg)


def params(cfg) -> int:
    """Weights and biases of the convs and the classifier."""
    return (sum(c.weights + c.co for c in convs(cfg)) + head_macs(cfg))


def forward_flops(cfg) -> int:
    """FLOPs of one image's forward pass."""
    return 2 * mult_adds(cfg)


def train_flops(cfg) -> int:
    """FLOPs of one image's forward and backward pass: every conv's forward
    and weight gradient, every input gradient but the stem's, and the
    classifier's three matmuls.  Nothing recomputed is counted."""
    total = 6 * head_macs(cfg)
    for c in convs(cfg):
        total += 2 * c.macs_per_image * (2 if c.family == "stem" else 3)
    return total


def flops(c: Conv, n: int) -> int:
    """FLOPs of one launch over ``n`` images, in any direction."""
    return 2 * n * c.macs_per_image


def bytes_moved(c: Conv, n: int, direction: str, precision: str) -> int:
    """Least HBM bytes of one launch over ``n`` images."""
    e = ITEMSIZE[precision]
    x = n * c.hi * c.hi * c.ci
    y = n * c.co if c.gap else n * c.ho * c.ho * c.co
    dy = n * c.ho * c.ho * c.co
    if direction == "fwd":
        return e * (x + c.weights + y) + 4 * c.co
    if direction == "dgrad":
        return e * (dy + c.weights + x)
    if direction == "wgrad":
        return e * (x + dy) + 4 * (c.weights + c.co)
    raise ValueError(f"unknown direction {direction!r}")


def least_seconds(c: Conv, n: int, direction: str, precision: str,
                  peak) -> tuple:
    """-> (seconds, bound): the larger of FLOPs over peak FLOP/s and bytes
    over HBM bandwidth, and which of the two it is."""
    t_flops = flops(c, n) / peak["flops_per_s"][precision]
    t_bytes = bytes_moved(c, n, direction, precision) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def directions(c: Conv, train: bool):
    if not train:
        return ("fwd",)
    return ("fwd", "wgrad") if c.family == "stem" else ("fwd", "dgrad", "wgrad")


def roofline_share(run, family: str):
    """Percent of the roofline that one kernel family reached in the traced
    window: the least time of its launches (at published widths, every
    layer of the family once per step and direction, at the rows one chip
    runs) over the device time of its events.  None where the trace holds
    no event of the family."""
    if run.trace is None:
        return None
    f = run.trace.families.get(family)
    if not f or not f["events"] or not f["seconds"]:
        return None
    train = run.traffic["kind"] == "train"
    rows = run.traffic["batch"] // run.traffic.get("data", 1)
    prec = run.cfg["precision"]
    layers = [c for c in convs(run.cfg) if c.family == family]
    least = sum(least_seconds(c, rows, d, prec, run.peak)[0]
                for c in layers for d in directions(c, train))
    launches = sum(len(directions(c, train)) for c in layers)
    return 100.0 * (f["events"] / launches) * least / f["seconds"]
