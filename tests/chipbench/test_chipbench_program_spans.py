"""The readers of the program's own spans: medians of the serving step's
phases, the host's share of the window and the compile count, read from
hand-made records inside a synthetic run's window."""
import statistics
import sys

import pytest

import chipbench_testutil  # noqa: F401  (the repo root on sys.path)
from chipbench import harness, program_spans, trace
from repro.utils import spans

MS = 1_000_000                  # nanoseconds
W0, W1 = 1_000 * MS, 1_100 * MS  # the window: 100 ms on time.time_ns()
PHASES = ("serve.stage", "serve.h2d", "serve.forward", "serve.readback",
          "serve.complete")
PHASE_METRICS = {"serve.stage_ms": "serve.stage",
                 "serve.h2d_ms": "serve.h2d",
                 "serve.forward_ms": "serve.forward",
                 "serve.readback_ms": "serve.readback"}


def make_run(window=True, traced=True):
    cell = harness.find(harness.load_benchmark()["workloads"],
                        "mnv1-0.25-128.serve", "workload")
    run = harness.Run(cell=cell, cfg={}, traffic={}, limits={}, seed=1,
                      seconds=0.1, tracing=traced, t_process=0.0)
    if window:
        run.spans.spans.append((trace.WINDOW, W0, W1))
    if traced:
        run.trace = trace.Reduced(window_s=0.1, busy_s=0.01, chips=1,
                                  ops={}, families={}, gaps={})
    return run


def steps(rec, starts_ms, durs_ms):
    """One step per start: phases of ``durs_ms`` (stage, h2d, forward,
    readback, complete) in order, then the step span."""
    for k, t in enumerate(starts_ms):
        a = W0 + t * MS
        sid = 100 + k
        for name, d in zip(PHASES, durs_ms[k]):
            rec.record(name, a, a + d * MS, 0, sid)
            a += d * MS
        rec.record("serve.step", W0 + t * MS, a, sid)


@pytest.fixture
def rec(monkeypatch):
    r = spans.Recorder(64)
    monkeypatch.setattr(spans, "recorder", lambda: r)
    return r


DURS = [(1, 2, 10, 0.5, 0.5), (3, 2, 12, 0.5, 0.5), (2, 4, 11, 1.5, 0.5)]


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_phase_medians(rec, metric):
    rec.record("serve.stage", W0 - 50 * MS, W0 - 10 * MS)   # before: out
    steps(rec, [0, 30, 60], DURS)
    rec.record("serve.stage", W1 + MS, W1 + 90 * MS)        # after: out
    col = PHASES.index(PHASE_METRICS[metric])
    want = sorted(d[col] for d in DURS)[1]
    got = harness.load_metric(metric).read(make_run())
    assert got == pytest.approx(want)


def test_host_share_is_the_step_outside_the_forward(rec):
    steps(rec, [0, 30, 60], DURS)
    got = harness.load_metric("serve.host_share").read(make_run())
    step = sum(sum(d) for d in DURS)
    fwd = sum(d[2] for d in DURS)
    assert got == pytest.approx(100.0 * (step - fwd) / 100.0)


def test_host_share_clips_a_step_at_the_window_end(rec):
    steps(rec, [90], [(2, 2, 10, 1, 1)])       # 90..106 ms, window ends at 100
    got = program_spans.host_share(make_run())
    assert got == pytest.approx(100.0 * (10 - 6) / 100.0)


@pytest.mark.parametrize("metric", ["compiles.serve", "compiles.train"])
def test_compiles_count_the_window_only(rec, metric):
    read = harness.load_metric(metric).read
    rec.record("compile.trace", W0 - 5 * MS, W0 - 4 * MS)     # warm-up
    rec.record("compile.backend", W0 - 4 * MS, W0 - MS)
    rec.record("serve.step", W0 + MS, W0 + 2 * MS, 1)         # not a compile
    assert read(make_run()) == 0
    rec.record("compile.trace", W0 + 10 * MS, W0 + 11 * MS)
    rec.record("compile.backend", W0 + 11 * MS, W0 + 40 * MS)
    assert read(make_run()) == 2


NEW = sorted(PHASE_METRICS) + ["serve.host_share", "compiles.serve",
                               "compiles.train"]


@pytest.mark.parametrize("metric", NEW)
def test_no_number_without_a_traced_window(rec, metric):
    steps(rec, [0, 30], DURS[:2])
    read = harness.load_metric(metric).read
    assert read(make_run(traced=False)) is None
    assert read(make_run(window=False)) is None


@pytest.mark.parametrize("metric", NEW)
def test_no_number_when_the_ring_dropped_the_window(monkeypatch, metric):
    r = spans.Recorder(4)
    monkeypatch.setattr(spans, "recorder", lambda: r)
    read = harness.load_metric(metric).read
    r.record("old", W0 - 9 * MS, W0 - 8 * MS)
    steps(r, [0], DURS[:1])                    # 6 records into a ring of 4
    assert r.dropped and r.lost_ns >= W0
    assert read(make_run()) is None


def test_drops_before_the_window_still_read(monkeypatch):
    r = spans.Recorder(8)
    monkeypatch.setattr(spans, "recorder", lambda: r)
    for i in range(6):
        r.record("old", W0 - 90 * MS + i, W0 - 80 * MS)
    steps(r, [0], DURS[:1])
    assert r.dropped == 4 and r.lost_ns < W0
    assert program_spans.median_ms(make_run(), "serve.forward") == 10.0


def test_no_number_from_a_missing_recorder(rec, monkeypatch):
    steps(rec, [0], DURS[:1])
    assert program_spans.compiles(make_run()) == 0
    # a program without the recorder, as a parent commit may be
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    monkeypatch.delattr(sys.modules["repro.utils"], "spans")
    for m in NEW:
        assert harness.load_metric(m).read(make_run()) is None


def test_no_steps_in_the_window_reads_none(rec):
    rec.record("compile.trace", W0 + MS, W0 + 2 * MS)
    assert program_spans.host_share(make_run()) is None
    assert program_spans.median_ms(make_run(), "serve.h2d") is None


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_a_phase_across_the_window_start_counts(rec, metric):
    """A step begun before the window: its phases that reach into the
    window count, those that ended before it do not."""
    straddler = (8, 4, 30, 1, 1)               # -10..-2 ms, then inside
    steps(rec, [-10], [straddler])
    steps(rec, [40, 60, 80], DURS)
    col = PHASES.index(PHASE_METRICS[metric])
    durs = [d[col] for d in DURS] + ([straddler[col]] if col else [])
    got = harness.load_metric(metric).read(make_run())
    assert got == pytest.approx(statistics.median(durs))


@pytest.mark.parametrize("edge", ["start", "end"])
def test_host_share_clips_a_step_at_either_window_edge(rec, edge):
    # a 40 ms step with 30 ms inside the window, and its 20 ms forward
    # with 18 ms inside
    a = W0 - 10 * MS if edge == "start" else W1 - 30 * MS
    f0 = a + (8 if edge == "start" else 12) * MS
    rec.record("serve.step", a, a + 40 * MS, 1)
    rec.record("serve.forward", f0, f0 + 20 * MS, 0, 1)
    got = program_spans.host_share(make_run())
    assert got == pytest.approx(100.0 * (30 - 18) / 100.0)
