"""Correctness of a serving run, driven on the CPU at a small size past
the look for a chip: a sound run is correct; the control (the plain reference at the
next lower operand precision, float8_e4m3fn, in the program's place) and
each fault the cell can have, planted under the timed path, are not.

Each cell's own limits are used.  The configurations keep every layer and
width of the cell's network; only the input side and the rows shrink.
"""
import numpy as np
import pytest

from chipbench_testutil import F8, SEED, make_run
from chipbench import serve


def serve_traffic(size):
    return {"kind": "serve", "image_size": size, "bucket": [size, size],
            "batch": 8, "data": 1, "rate_per_s": 40, "pool_images": 16,
            "check_sample": 64, "ref_block": 16}


SERVE_CELLS = [("mnv1-1.0-224.serve", 32), ("mnv1-0.25-128.serve", 32)]


@pytest.mark.parametrize("cell,size", SERVE_CELLS)
def test_serve_sound_run_is_correct_and_its_control_is_not(cell, size):
    run = make_run(cell, size, serve_traffic(size))
    st = serve.Setup(run, SEED)
    reqs, t0, due, late = serve.window(run, st, SEED, 40, 1.0)
    serve.summarize(run, st, reqs, t0, due, late)
    errs = serve.errors(run, st, reqs, SEED, (F8,))
    lim = run.limits["logit_err"]
    assert run.complete and run.failed == 0
    assert errs["program"] <= lim < errs[str(F8)]


def _broken_answer(monkeypatch):
    from repro.launch.conv_serve import ConvServer
    execute = ConvServer._execute

    def altered(self, bucket, imgs):
        out = np.array(execute(self, bucket, imgs), np.float32)
        out[0] = out[0][::-1]            # one answer altered as it is made
        return out
    monkeypatch.setattr(ConvServer, "_execute", altered)


def _dropped_answer(monkeypatch):
    from repro.serve.scheduler import SlotPool
    drain = SlotPool.drain
    dropped = []

    def lossy(self, bucket):
        batch = drain(self, bucket)
        if batch and not dropped:
            dropped.append(batch.pop())  # one request never answered
        return batch
    monkeypatch.setattr(SlotPool, "drain", lossy)


@pytest.mark.parametrize("fault", [_broken_answer, _dropped_answer])
def test_serve_fault_makes_the_run_incorrect(monkeypatch, fault):
    cell, size = SERVE_CELLS[0]
    run = make_run(cell, size, serve_traffic(size))
    fault(monkeypatch)
    serve.run(run)
    assert not run.correct


