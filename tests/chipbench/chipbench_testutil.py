"""Shared by the chip benchmark's CPU tests, which import it first: it puts
the repo root on ``sys.path`` so that ``chipbench`` imports.  (A
``conftest.py`` here would shadow ``tests/conftest.py`` for the test modules
that import from it by name.)"""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness  # noqa: E402

F8 = jnp.dtype("float8_e4m3fn")
SEED = 2 ** 31 + 12_345          # larger than 32 signed bits hold


def make_run(cell, size, traffic):
    """A run of ``cell`` on the CPU: its configuration with every layer and
    width, at a ``size`` x ``size`` input, under ``traffic``."""
    c = harness.find(harness.load_benchmark()["workloads"], cell, "workload")
    cfg = dict(harness.load_config(c["config"]), input_size=size)
    return harness.Run(cell=c, cfg=cfg, traffic=traffic,
                       limits=harness.load_limits(cell), seed=SEED,
                       seconds=1.0, tracing=False,
                       t_process=time.perf_counter(), devices=jax.devices(),
                       peak=harness.peak_for("TPU v5 lite"))
