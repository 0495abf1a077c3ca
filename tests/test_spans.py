"""The program's span recorder (DESIGN.md §18): a bounded ring on the
profiler's clock, the phases of ``ConvServer.step`` and the compile
counter."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.context import ConvContext
from repro.core.errors import KernelLaunchError
from repro.launch.conv_serve import ConvServer
from repro.launch.mesh import make_mesh_auto
from repro.nn.conv import BlockedCNN, BlockedConv2D
from repro.nn.module import init_tree
from repro.serve import ConvRequest, Outcome
from repro.utils import spans
from repro.utils.faults import FaultPlan, FaultRule, fault_plan

PHASES = ("serve.stage", "serve.h2d", "serve.forward", "serve.readback",
          "serve.complete")
SIZES = [(6, 6), (5, 6), (8, 8), (7, 7), (6, 5), (8, 6), (4, 4), (8, 8),
         (6, 6)]


@pytest.fixture(autouse=True)
def recorder():
    """The process-wide recorder, cleared before and after."""
    spans.clear()
    yield spans.recorder()
    spans.clear()


def make_server(**kw):
    model = BlockedCNN(convs=(BlockedConv2D(ci=8, co=16, lane=8),),
                       n_classes=3)
    params = init_tree(model.specs(), jax.random.PRNGKey(0))
    mesh = make_mesh_auto((1,), ("data",))
    server = ConvServer(model, params, mesh, [(6, 6), (8, 8)], batch=2,
                        context=ConvContext(impl="jnp"), **kw)
    server.warmup()
    return server


def serve_ragged(server):
    """Submit a ragged trace, stepping as it arrives; -> requests."""
    rng = np.random.default_rng(7)
    reqs = []
    for i, (h, w) in enumerate(SIZES):
        r = ConvRequest(rid=i, image=rng.normal(size=(h, w, 8))
                        .astype(np.float32))
        server.submit(r)
        reqs.append(r)
        if i % 3 == 2:
            server.step()
    server.run()
    return reqs


def by_name(records, name):
    return [r for r in records if r[0] == name]


def test_ring_is_bounded_and_counts_drops():
    rec = spans.Recorder(4)
    for i in range(6):
        rec.record("x", 10 * i, 10 * i + 5, span_id=i)
    got = rec.spans()
    assert len(got) == 4 and len(rec._buf) == 4
    assert [r[3] for r in got] == [2, 3, 4, 5]          # oldest first
    assert rec.dropped == 2
    assert rec.lost_ns == 15                            # end of rid 1
    rec.clear()
    assert rec.spans() == [] and rec.dropped == 0 and rec.lost_ns == -1


def test_ring_below_capacity_drops_nothing():
    rec = spans.Recorder(8)
    for i in range(8):
        rec.record("x", i, i + 1)
    assert [r[1] for r in rec.spans()] == list(range(8))
    assert rec.dropped == 0 and rec.lost_ns == -1
    with pytest.raises(ValueError):
        spans.Recorder(0)


def test_process_ring_holds_a_busy_window():
    # ~90 steps/s with six spans each for 30 s: the busiest serving cell's
    # window fits four times
    assert spans.RING >= 4 * 30 * 90 * 6


def test_records_are_stamped_on_the_wall_clock():
    assert spans.now is time.time_ns
    server = make_server(clock=iter(range(10 ** 6)).__next__)
    t0 = time.time_ns()
    server.submit(ConvRequest(rid=0, image=np.zeros((6, 6, 8), np.float32)))
    server.step()
    t1 = time.time_ns()
    (step,) = by_name(spans.spans(), "serve.step")
    assert t0 <= step[1] <= step[2] <= t1
    # the injected counter clock stamps the request, not the spans
    assert server.completed[0].t_done < 100


def test_every_step_has_its_five_phases_in_order():
    server = make_server()
    spans.clear()
    serve_ragged(server)
    recs = spans.spans()
    steps = by_name(recs, "serve.step")
    assert len(steps) >= 5                        # ragged: both buckets ran
    assert {s[5]["bucket"] for s in steps} == {(6, 6), (8, 8)}
    for s in steps:
        name, a, b, sid, cause, attrs = s
        assert sid > 0 and cause == 0
        assert 1 <= attrs["rows"] <= attrs["executed"] == 2
        kids = [r for r in recs if r[4] == sid]
        assert tuple(k[0] for k in kids) == PHASES
        assert kids[0][1] == a and kids[-1][2] == b
        for k, nxt in zip(kids, kids[1:]):
            assert k[1] <= k[2] == nxt[1]
        total = sum(k[2] - k[1] for k in kids)
        assert total == b - a
    assert sum(s[5]["rows"] for s in steps) == len(SIZES)


def test_forward_records_its_path():
    server = make_server(max_retries=1)
    spans.clear()
    plan = FaultPlan((FaultRule(site="serve.step", error=KernelLaunchError,
                                visits=(0, 1)),), seed=0)
    with fault_plan(plan):
        server.submit(ConvRequest(rid=0, image=np.ones((6, 6, 8), np.float32)))
        server.step()
        server.submit(ConvRequest(rid=1, image=np.ones((6, 6, 8), np.float32)))
        server.step()
    first, second = by_name(spans.spans(), "serve.forward")
    assert first[5] == {"path": "degraded", "attempts": 3}
    assert second[5] == {"path": "primary", "attempts": 1}
    assert server.health()["degraded_steps"] == 1


def test_a_warm_server_compiles_nothing():
    server = make_server()
    spans.clear()
    serve_ragged(server)
    recs = spans.spans()
    assert not [r for r in recs if r[0] in spans.COMPILE_EVENTS]


def test_compiles_are_counted_inside_a_window():
    def window(fn):
        w0 = time.time_ns()
        fn()
        w1 = time.time_ns()
        return sum(r[0] in spans.COMPILE_EVENTS and w0 <= r[1] <= w1
                   for r in spans.spans())

    f = jax.jit(lambda x: jnp.sin(x) * 3.0)
    x = jnp.ones((5, 3))
    f(x).block_until_ready()
    assert window(lambda: f(jnp.ones((7, 3))).block_until_ready()) >= 1
    assert window(lambda: f(x).block_until_ready()) == 0
    backend = [r for r in spans.spans() if r[0] == "compile.backend"]
    assert backend and all(r[1] <= r[2] for r in backend)


def test_compile_events_name_their_function():
    def tripled(x):
        return x * 3.0

    jax.jit(tripled)(jnp.ones((3, 11))).block_until_ready()
    traced = [r for r in spans.spans() if r[0] == "compile.trace"]
    assert any(r[5]["fun"] and "tripled" in r[5]["fun"] for r in traced)
    assert all(r[3] == 0 and r[4] == 0 for r in traced)


def test_span_ids_are_fresh_and_positive():
    ids = [spans.new_id() for _ in range(5)]
    assert len(set(ids)) == 5 and min(ids) > 0


def test_a_step_puts_its_batch_on_the_device_once(monkeypatch):
    """Retries and the degraded executable reuse the step's device batch."""
    server = make_server(max_retries=2)
    spans.clear()
    puts = []
    real = jax.device_put

    def counting(x, *a, **kw):
        puts.append(np.shape(x))
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counting)
    plan = FaultPlan((FaultRule(site="serve.step", error=KernelLaunchError,
                                visits=(0, 1, 2, 3)),), seed=0)
    with fault_plan(plan):
        for rid in range(2):
            server.submit(ConvRequest(rid=rid,
                                      image=np.ones((6, 6, 8), np.float32)))
            server.step()
    assert puts == [(2, 6, 6, 8)] * 2
    fwd = by_name(spans.spans(), "serve.forward")
    assert [f[5] for f in fwd] == [{"path": "degraded", "attempts": 4},
                                   {"path": "primary", "attempts": 2}]
    assert len(by_name(spans.spans(), "serve.h2d")) == 2


def test_an_idle_step_records_nothing():
    server = make_server(clock=iter(range(10 ** 6)).__next__)
    spans.clear()
    assert not server.step()
    server.submit(ConvRequest(rid=0, image=np.zeros((6, 6, 8), np.float32)),
                  timeout=0.5)
    assert not server.step()                      # the request expired
    assert server.completed[0].outcome is Outcome.TIMED_OUT
    assert spans.spans() == []


def test_expired_requests_are_not_rows():
    server = make_server(clock=iter(range(10 ** 6)).__next__)
    spans.clear()
    server.submit(ConvRequest(rid=0, image=np.zeros((6, 6, 8), np.float32)),
                  timeout=0.5)
    server.submit(ConvRequest(rid=1, image=np.zeros((6, 6, 8), np.float32)))
    assert server.step()
    (step,) = by_name(spans.spans(), "serve.step")
    assert step[5]["rows"] == 1 and step[5]["executed"] == 2
    assert [r.outcome for r in server.completed] == [Outcome.TIMED_OUT,
                                                     Outcome.OK]


def test_health_keeps_its_keys():
    server = make_server()
    serve_ragged(server)
    assert set(server.health()) == {
        "submitted", "ok", "shed", "timed_out", "retries",
        "transient_faults", "degraded_steps", "admit_faults", "steps",
        "queue_depth", "pending", "shed_rate", "timeout_rate", "occupancy",
        "breakers"}
