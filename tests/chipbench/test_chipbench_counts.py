"""``chipbench/counts.py`` against hand counts and the published totals."""
import pytest

import chipbench_testutil  # noqa: F401  (the repo root on sys.path)
from chipbench import counts, harness

CONFIGS = ("mobilenet_v1-1.0-224", "mobilenet_v1-0.25-128")


def layer(cfg, name):
    return next(c for c in counts.convs(cfg) if c.name == name)


@pytest.mark.parametrize("name,mult_adds_m,params_m", [
    ("mobilenet_v1-1.0-224", 569, 4.2),    # Howard et al. 2017, Table 8
    ("mobilenet_v1-0.25-128", 14, 0.47),   # released 0.25_128 checkpoint
])
def test_published_totals(name, mult_adds_m, params_m):
    cfg = harness.load_config(name)
    assert round(counts.mult_adds(cfg) / 1e6) == mult_adds_m
    assert round(counts.params(cfg) / 1e6, 2 if params_m < 1 else 1) == params_m
    assert counts.forward_flops(cfg) == 2 * counts.mult_adds(cfg)


# (config, layer, hi, ci, co, k, stride, groups, ho) by hand from Table 1
HAND = [
    ("mobilenet_v1-1.0-224", "conv0", 224, 3, 32, 3, 2, 1, 112),
    ("mobilenet_v1-1.0-224", "conv2.dw", 112, 64, 64, 3, 2, 64, 56),
    ("mobilenet_v1-1.0-224", "conv12.pw", 7, 512, 1024, 1, 1, 1, 7),
    ("mobilenet_v1-0.25-128", "conv0", 128, 3, 8, 3, 2, 1, 64),
    ("mobilenet_v1-0.25-128", "conv2.dw", 64, 16, 16, 3, 2, 16, 32),
    ("mobilenet_v1-0.25-128", "conv12.pw", 4, 128, 256, 1, 1, 1, 4),
]


@pytest.mark.parametrize("name,lay,hi,ci,co,k,s,g,ho", HAND)
def test_layer_counts_by_hand(name, lay, hi, ci, co, k, s, g, ho):
    cfg = harness.load_config(name)
    c = layer(cfg, lay)
    assert (c.hi, c.ci, c.co, c.k, c.stride, c.groups, c.ho) == (
        hi, ci, co, k, s, g, ho)
    n = 32
    macs = n * ho * ho * co * (ci // g) * k * k
    assert counts.flops(c, n) == 2 * macs
    w = k * k * (ci // g) * co
    assert counts.bytes_moved(c, n, "fwd", "bf16") == (
        2 * (n * hi * hi * ci + w + n * ho * ho * co) + 4 * co)
    assert counts.bytes_moved(c, n, "dgrad", "bf16") == (
        2 * (n * ho * ho * co + w + n * hi * hi * ci))
    assert counts.bytes_moved(c, n, "wgrad", "bf16") == (
        2 * (n * hi * hi * ci + n * ho * ho * co) + 4 * (w + co))


def test_stem_1_0_224_numbers():
    """Stem of 1.0-224 at batch 32: 112*112*32*27 MACs per image."""
    c = layer(harness.load_config("mobilenet_v1-1.0-224"), "conv0")
    assert c.macs_per_image == 10_838_016
    assert counts.flops(c, 32) == 693_633_024
    # bf16 input 32*224*224*3, weights 3*3*3*32, output 32*112*112*32;
    # f32 bias 32
    assert counts.bytes_moved(c, 32, "fwd", "bf16") == (
        2 * (4_816_896 + 864 + 12_845_056) + 128)


def test_gap_fused_last_pointwise_writes_pooled_features():
    cfg = harness.load_config("mobilenet_v1-1.0-224")
    last = counts.convs(cfg)[-1]
    assert last.gap and last.family == "pointwise"
    assert counts.bytes_moved(last, 1, "fwd", "bf16") == (
        2 * (7 * 7 * 1024 + 1024 * 1024 + 1024) + 4 * 1024)


def test_least_time_names_its_bound():
    cfg = harness.load_config("mobilenet_v1-1.0-224")
    peak = harness.peak_for("TPU v5 lite")
    pw = layer(cfg, "conv13.pw")        # 1024x1024 matmul over 49 rows/image
    dw = layer(cfg, "conv1.dw")         # 9 MACs per element: bytes bound
    assert counts.least_seconds(pw, 32, "fwd", "bf16", peak)[1] == "compute"
    assert counts.least_seconds(dw, 32, "fwd", "bf16", peak)[1] == "memory"


@pytest.mark.parametrize("name", CONFIGS)
def test_train_flops_are_three_forwards_less_the_stem_dgrad(name):
    cfg = harness.load_config(name)
    stem = counts.convs(cfg)[0]
    assert counts.train_flops(cfg) == (3 * counts.forward_flops(cfg)
                                       - 2 * stem.macs_per_image)
