#!/usr/bin/env python3
"""Readings the correctness limits are set from, and the knee sweep.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 2]
        [--control float8_e4m3fn] [--half-batch]
    python chipbench/calibrate.py --workload <cell> --seeds 1 --sweep 800,1200

One process, one chip.  For each seed it builds the cell's set-up anew and
reads the numbers the run compares: for a serving cell after a short window
at the cell's own rate, for a training cell after the first three steps.
``--control`` also reads the same numbers of the plain reference computed
at that operand dtype in the program's place; ``--half-batch`` (training)
those of the reference that averages over half of each batch.  ``--sweep``
offers each rate for ``--seconds`` and prints the latency, the completed
rate and how late the generator ran, to find the knee.  One JSON line per
seed or rate goes to standard output.  The benchmark's own runs do not run
this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def serve_seed(run, seed, seconds, controls):
    import numpy as np
    from chipbench import serve
    t = time.perf_counter()
    st = serve.Setup(run, seed)
    setup = time.perf_counter() - t
    reqs, t0, due, late = serve.window(run, st, seed, run.traffic["rate_per_s"],
                                       seconds)
    run.seconds = seconds
    serve.summarize(run, st, reqs, t0, due, late)
    out = {"seed": seed, "setup_s": setup, "complete": run.complete,
           "failed": run.failed, **run.e2e,
           "late_p95_ms": float(np.percentile(late, 95) * 1e3)}
    t = time.perf_counter()
    out["errors"] = serve.errors(run, st, reqs, seed, controls)
    out["check_s"] = time.perf_counter() - t
    return out


def train_seed(run, seed, controls, half_batch):
    from chipbench import train
    t = time.perf_counter()
    st = train.Setup(run, seed)
    out = {"seed": seed, "setup_s": time.perf_counter() - t,
           "losses": st.losses}
    t = time.perf_counter()
    out["errors"] = train.errors(run, st, controls, half_batch)
    out["check_s"] = time.perf_counter() - t
    return out


def sweep(run, seed, rates, seconds):
    import numpy as np
    from chipbench import serve
    st = serve.Setup(run, seed)
    for rate in rates:
        run.seconds = seconds
        run.e2e = {}
        reqs, t0, due, late = serve.window(run, st, seed, rate, seconds)
        serve.summarize(run, st, reqs, t0, due, late)
        yield {"rate": rate, **run.e2e, "failed": run.failed,
               "steps": len(run.steps),
               "occupancy": run.counters["occupancy"],
               "late_p95_ms": float(np.percentile(late, 95) * 1e3),
               "late_max_ms": float(late.max() * 1e3)}
        st.server.completed.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--half-batch", action="store_true")
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    from chipbench.run import prepare
    from chipbench import harness
    try:
        _, _, run = prepare(["--workload", args.workload, "--seed", "0",
                             "--seconds", str(args.seconds)], T_PROCESS)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax.numpy as jnp
    controls = [jnp.dtype(c) for c in args.control]
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sweep:
        rows = sweep(run, seeds[0], [float(r) for r in args.sweep.split(",")],
                     args.seconds)
    elif run.traffic["kind"] == "serve":
        rows = (serve_seed(run, s, args.seconds, controls) for s in seeds)
    else:
        rows = (train_seed(run, s, controls, args.half_batch) for s in seeds)
    for row in rows:
        print(json.dumps(row, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
