"""Correctness of a training run, driven on the CPU at a small size past
the look for a chip: a sound run is correct; the control (the plain reference at the
next lower operand precision, float8_e4m3fn, in the program's place) and
each fault the cell can have, planted under the timed path, are not.

Each cell's own limits are used.  The configurations keep every layer and
width of the cell's network; only the input side and the rows shrink.
"""
import jax
import pytest

from chipbench_testutil import F8, SEED, make_run
from chipbench import harness, train


TRAIN_CELL, TRAIN_SIZE = "mnv1-1.0-224.train", 32


def train_traffic():
    t = harness.load_traffic(harness.find(
        harness.load_benchmark()["workloads"], TRAIN_CELL, "workload")["traffic"])
    return dict(t, batch=16, distinct_batches=3, ref_block=8)


def test_train_sound_run_is_correct_and_its_control_is_not():
    run = make_run(TRAIN_CELL, TRAIN_SIZE, train_traffic())
    st = train.Setup(run, SEED)
    errs = train.errors(run, st, (F8,))
    assert all(errs["program"][k] <= v for k, v in run.limits.items())
    assert any(errs[str(F8)][k] > v for k, v in run.limits.items())


def _unchanged_state(monkeypatch):
    from repro.train import trainstep
    make = trainstep.make_train_step

    def frozen(*a, **kw):
        step = make(*a, **kw)

        def same(params, state, batch):
            _, _, metrics = step(params, state, batch)
            return params, state, metrics
        return same
    monkeypatch.setattr(trainstep, "make_train_step", frozen)


def _half_batch(monkeypatch):
    from repro.train import trainstep
    make = trainstep.make_train_step

    def half(*a, **kw):
        step = make(*a, **kw)

        def first_half(params, state, batch):
            return step(params, state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return first_half
    monkeypatch.setattr(trainstep, "make_train_step", half)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_train_fault_makes_the_run_incorrect(monkeypatch, fault):
    run = make_run(TRAIN_CELL, TRAIN_SIZE, train_traffic())
    fault(monkeypatch)
    train.run(run)
    assert not run.correct
