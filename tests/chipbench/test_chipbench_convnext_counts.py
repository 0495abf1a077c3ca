"""The work ConvNeXt-T needs, counted by hand (``families/convnext_counts``).

Liu et al. 2022 tabulate ConvNeXt-T at 28M parameters and 4.5G FLOPs (mult-
adds); counted layer by layer the network has 28,589,128 parameters and
4,455,531,264 mult-adds.  Each reader's share is its least time over the
device time it is given, at published widths.
"""
import pytest

import chipbench_testutil  # noqa: F401  (the repo root on sys.path)
from chipbench import harness, trace
from chipbench.families import convnext_counts as cc

CFG = harness.load_config("convnext_t-224")
PEAK = harness.peak_for("TPU v5 lite")


def test_published_totals_by_hand():
    stem = 4 * 4 * 3 * 96 + 96 + 2 * 96
    blocks = sum(d * (49 * c + c + 2 * c + 2 * (4 * c * c) + 4 * c + c + c)
                 for d, c in ((3, 96), (3, 192), (9, 384), (3, 768)))
    downs = sum(2 * ci + 4 * ci * co + co
                for ci, co in ((96, 192), (192, 384), (384, 768)))
    head = 2 * 768 + 768 * 1000 + 1000
    assert stem + blocks + downs + head == cc.params(CFG) == 28_589_128
    macs = (56 * 56 * 96 * 3 * 16
            + sum(d * h * h * (49 * c + 8 * c * c)
                  for d, c, h in ((3, 96, 56), (3, 192, 28), (9, 384, 14),
                                  (3, 768, 7)))
            + sum(h * h * 4 * ci * co
                  for ci, co, h in ((96, 192, 28), (192, 384, 14),
                                    (384, 768, 7)))
            + 768 * 1000)
    assert macs == cc.mult_adds(CFG) == 4_455_531_264
    pub = CFG["published"]
    assert abs(cc.params(CFG) / pub["params"] - 1) < 0.01
    assert abs(cc.mult_adds(CFG) / pub["mult_adds"] - 1) < 0.01


def test_layers_and_norms():
    convs = cc.convs(CFG)
    fam = [c.family for c in convs]
    assert (fam.count("stem"), fam.count("window"), fam.count("depthwise"),
            fam.count("pointwise")) == (1, 3, 18, 36)
    assert all(c.k == 7 and c.groups == c.ci for c in convs
               if c.family == "depthwise")
    assert [c.ho for c in convs if c.family == "window"] == [28, 14, 7]
    assert [c.name for c in convs if c.gap] == ["s3b2.pw2"]
    norms = cc.norms(CFG)
    assert len(norms) == 23
    assert norms[0] == cc.Norm("stem.norm", 96, 56 * 56)
    assert norms[-1] == cc.Norm("head.norm", 768, 1)
    assert [n.c for n in norms if n.name.startswith("down")] == [96, 192, 384]
    # bf16 maps: x and y forward, x, dy and dx backward; f32 affine
    n = norms[0]
    assert n.bytes_moved(128, "fwd", "bf16") == 2 * 2 * 128 * 3136 * 96 + 768
    assert n.bytes_moved(128, "bwd", "bf16") == 3 * 2 * 128 * 3136 * 96 + 768


def test_train_flops_are_three_forwards_less_the_stem_dgrad():
    stem = cc.convs(CFG)[0].macs_per_image
    assert cc.train_flops(CFG) == 6 * cc.mult_adds(CFG) - 2 * stem


class _Run:
    def __init__(self, seconds, families):
        self.cfg, self.peak, self.cell = CFG, PEAK, {"chips": 1}
        self.traffic = harness.load_traffic("train-224-b128")
        self.counters = {"steps": 10, "images": 1280, "elapsed_s": 3.0}
        self.trace = trace.Reduced(window_s=3.0, busy_s=3.0, chips=1,
                                   ops=seconds, families=families, gaps={})


@pytest.mark.parametrize("metric,family", [
    ("cnx.dw_roofline.train", "depthwise"),
    ("cnx.pw_roofline.train", "pointwise"),
    ("cnx.ln_roofline.train", "layer_norm")])
def test_roofline_readers_divide_least_time_by_device_time(metric, family):
    least = 10 * cc.least_per_step(_Run({}, {}), family)
    ops = {"jvp_jit_layer_norm_blocked_pallas__.4": least,
           "transpose_jvp_jit_layer_norm_bwd_pallas___.9": least,
           "fusion.3": 5.0}
    fams = {f: {"events": 1, "seconds": 2 * least}
            for f in ("depthwise", "pointwise")}
    got = harness.load_metric(metric).read(_Run(ops, fams))
    assert got == pytest.approx(50.0)
    assert harness.load_metric(metric).read(_Run({}, {})) is None


def test_mfu_reader():
    got = harness.load_metric("cnx.mfu.train").read(_Run({}, {}))
    want = 100 * 1280 * cc.train_flops(CFG) / 3.0 / 197e12
    assert got == pytest.approx(want)
