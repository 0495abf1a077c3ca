#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name (``chipbench/harness.py``).  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with the reference beside its limit, which
also end standard error.
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


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(argv, t_process):
    """Parse, find the cell's parts, take the chip; -> (args, bench, run)."""
    from chipbench import harness
    args = parse(argv)
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    cfg = harness.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    limits = harness.load_limits(cell["name"])
    import jax
    devices, _, kind = harness.check_devices(jax, cell["chips"])
    from repro.utils.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = harness.Run(cell=cell, cfg=cfg, traffic=traffic, limits=limits,
                      seed=args.seed, seconds=args.seconds,
                      tracing=bool(args.trace), t_process=t_process,
                      devices=devices, peak=harness.peak_for(kind))
    return args, bench, run


def report(bench, run) -> str:
    """Per-layer or end-to-end metrics, the device and the result line."""
    import jax
    from chipbench import harness
    name = run.cell["name"]
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    metrics, breakdown = {}, None
    if run.tracing:
        for m in harness.metrics_of(bench, name, "per_layer"):
            v = harness.load_metric(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    else:
        for m in harness.metrics_of(bench, name, "end_to_end"):
            if m["name"] in run.e2e:
                metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                      "unit": m["unit"]}
    return harness.result(run, metrics, device, breakdown)


def main(argv=None) -> int:
    from chipbench import harness
    try:
        args, bench, run = prepare(argv, T_PROCESS)
    except harness.NoChip as e:
        print(f"chipbench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    harness.runner(run.traffic["kind"]).run(run)
    line = report(bench, run)
    sys.stderr.flush()
    for k, (v, lim) in run.checks.items():
        print(f"[check] {k} {v!r} limit {lim!r}", file=sys.stderr)
    print(f"[check] correct {json.dumps(run.correct)}", file=sys.stderr,
          flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
