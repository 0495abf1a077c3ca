"""The reduction from a profiler trace to busy time, idle share, kernel
family time and idle gaps named by the host's spans."""
import pathlib

import pytest

import chipbench_testutil  # noqa: F401  (the repo root on sys.path)
from chipbench import harness, trace

FIXTURE = pathlib.Path(__file__).parent / "data"
MS = 1_000_000          # nanoseconds


def planes(device_events, host_spans, chips=1):
    return ([{"name": "/host:CPU", "lines": [
                {"name": "python", "events": host_spans}]}]
            + [{"name": f"/device:TPU:{i}", "lines": [
                {"name": "XLA Modules", "events": [("jit_fwd", 0, 100 * MS)]},
                {"name": "XLA Ops", "events": device_events}]}
               for i in range(chips)])


FAMILIES = {"pointwise_conv2d_blocked_pallas": "pointwise",
            "depthwise_conv2d_blocked_pallas": "depthwise"}
PW = "%pointwise_conv2d_blocked_pallas.13 = bf16[32,1,112,112,64] custom-call(...)"
DW = "%depthwise_conv2d_blocked_pallas.4 = bf16[32,1,112,112,128] custom-call(...)"


def test_busy_is_the_union_and_gaps_take_the_innermost_span():
    ops = [(PW, 10 * MS, 10 * MS),                   # 10-20
           ("%fusion.1 = f32[8] fusion(...)", 15 * MS, 10 * MS),  # overlaps
           (DW, 40 * MS, 20 * MS),                   # 40-60
           (PW, 95 * MS, 20 * MS)]                   # 95-115, clipped to 100
    spans = [(trace.WINDOW, 0, 100 * MS),
             ("bench.step", 0, 30 * MS),
             ("bench.wait_arrival", 25 * MS, 15 * MS),   # 25-40
             ("bench.step", 60 * MS, 40 * MS),
             ("other", 60 * MS, 40 * MS)]                # not the bench's
    r = trace.reduce_planes(planes(ops, spans), FAMILIES)
    assert r.window_s == pytest.approx(0.1)
    # busy: 10-25, 40-60, 95-100
    assert r.busy_s == pytest.approx(0.040)
    assert r.idle_share == pytest.approx(0.6)
    assert r.families["pointwise"] == {"events": 2, "seconds": pytest.approx(0.015)}
    assert r.families["depthwise"]["seconds"] == pytest.approx(0.020)
    assert r.ops["fusion.1"] == pytest.approx(0.010)
    # idle: 0-10 (step), 25-40 (wait_arrival, inside nothing smaller),
    # 60-95 (step)
    assert r.gaps == {"bench.step": pytest.approx(0.045),
                      "bench.wait_arrival": pytest.approx(0.015)}
    b = r.breakdown()
    assert b["device_ops"][0] == ["depthwise_conv2d_blocked_pallas.4",
                                  pytest.approx(0.020)]
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(0.045)]


def test_chips_are_averaged():
    ops = [(PW, 0, 50 * MS)]
    r = trace.reduce_planes(
        planes(ops, [(trace.WINDOW, 0, 100 * MS)], chips=4), FAMILIES)
    assert r.chips == 4
    assert r.busy_s == pytest.approx(0.05)
    assert r.families["pointwise"]["events"] == 1
    assert r.gaps == {trace.WINDOW: pytest.approx(0.05)}


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(planes([], []), FAMILIES)
    with pytest.raises(ValueError, match="device"):
        trace.reduce_planes(planes([], [(trace.WINDOW, 0, MS)])[:1], FAMILIES)


def test_op_names():
    assert trace.op_name(PW) == "pointwise_conv2d_blocked_pallas.13"
    assert trace.base_name("pointwise_dgrad_pallas.13") == "pointwise_dgrad_pallas"
    assert trace.base_name("broadcast_select_fusion") == "broadcast_select_fusion"


def test_kernel_table_maps_every_direction_to_its_family():
    fams = harness.kernel_families()
    for fam in ("pointwise", "depthwise"):
        names = [f"{fam}_conv2d_blocked_pallas",
                 f"jvp_jit_{fam}_conv2d_blocked_pallas__",
                 f"{fam}_dgrad_pallas", f"{fam}_wgrad_pallas"]
        assert [fams[n] for n in names] == [fam] * 4


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A device-only trace of 36 engine steps of the 0.25-128 serving cell
    on a TPU v5e, with the benchmark's wall-clock spans of that run."""
    import gzip
    import json
    path = tmp_path_factory.mktemp("trace") / "serve.xplane.pb"
    path.write_bytes(gzip.decompress(
        (FIXTURE / "serve_0.25_128.xplane.pb.gz").read_bytes()))
    planes = trace.load(str(path))
    spans = trace.Spans()
    spans.spans = [tuple(s) for s in json.loads(
        (FIXTURE / "serve_0.25_128.spans.json").read_text())["spans"]]
    return planes, spans


def test_recorded_trace_reduces_by_hand(recorded):
    planes, spans = recorded
    start = trace.profile_start_ns(planes)
    reduced = trace.reduce_planes(planes + [spans.plane(start)],
                                  harness.kernel_families())
    (w0, w1), = [(a - start, b - start) for n, a, b in spans.spans
                 if n == trace.WINDOW]
    ops = [(s, s + d) for _, evs in trace.device_lines(planes)
           for _, s, d in evs]
    assert len(trace.device_lines(planes)) == 1
    # the union of the clipped intervals, by a sweep apart from trace's own
    clipped = sorted((max(s, w0), min(e, w1)) for s, e in ops
                     if min(e, w1) > max(s, w0))
    busy, reach = 0.0, w0
    for s, e in clipped:
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    assert reduced.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert reduced.busy_s == pytest.approx(busy * 1e-9)
    assert 0.0 < reduced.idle_share < 1.0
    steps = sum(n == "bench.step" for n, _, _ in spans.spans)
    # one launch of each of the 13 pointwise and 13 depthwise convs a step
    assert reduced.families["pointwise"]["events"] == 13 * steps
    assert reduced.families["depthwise"]["events"] == 13 * steps
    assert reduced.families["window"]["events"] == steps      # the stem
    fam_s = sum(f["seconds"] for f in reduced.families.values())
    assert fam_s < reduced.busy_s
    # idle time splits over the bench's spans and adds up to the window
    assert set(reduced.gaps) <= {"bench.step", "bench.wait_arrival",
                                 trace.WINDOW}
    assert sum(reduced.gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)
