"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1.*   packing-overhead split (paper Fig. 1); derived = packing fraction
  fig4.*   direct vs im2col vs FFT (paper Fig. 4); derived = im2col/direct
  fig5.*   parallel-width scaling (paper Fig. 5, TPU-native form);
           derived = GEMM-path collective bytes per chip (direct path: 0)
  mem.*    zero-overhead table (paper §1/§4); derived = im2col overhead
           as a multiple of the irreducible tensors
  roofline.* summary per dry-run cell (if artifacts exist);
           derived = roofline fraction
"""
from __future__ import annotations

import argparse
import os


def emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer layers/iterations")
    ap.add_argument("--skip-fig5", action="store_true")
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    args = ap.parse_args()
    from repro.utils.cache import enable_compile_cache
    enable_compile_cache()

    from .cnn_zoo import ALEXNET, ZOO
    from .fig_conv import bench_fig1_packing_split, bench_fig4
    from .memory_table import bench_memory

    iters = 2 if args.quick else 3
    zoo = ALEXNET if args.quick else ZOO

    for row in bench_fig1_packing_split(ALEXNET[:3] if args.quick else ALEXNET,
                                        iters=iters):
        emit(f"fig1.{row['layer']}", row["im2col_total_us"],
             f"packing_fraction={row['packing_fraction']:.3f}")

    for row in bench_fig4(zoo, iters=iters):
        # two 'direct' columns: our blocked/MXU-shaped formulation, and XLA's
        # native direct conv (Eigen spatial conv — the CPU-idiomatic direct
        # implementation, paper's own comparison on CPUs)
        emit(f"fig4.{row['layer']}", row["direct_us"],
             f"im2col_over_blocked_direct={row['direct_vs_im2col']:.2f};"
             f"im2col_over_native_direct={row['im2col_us'] / row['lax_us']:.2f}")

    for row in bench_memory(zoo, empirical=not args.quick):
        emit(f"mem.{row['layer']}", 0.0,
             f"im2col_overhead_x={row['im2col_vs_base']:.2f}")

    if not args.skip_fig5:
        from .fig5_scaling import bench_fig5
        for row in bench_fig5((1, 4, 16) if args.quick else (1, 2, 4, 8, 16)):
            if "error" in row:
                emit(f"fig5.width{row['n']}", 0.0, "ERROR")
                continue
            emit(f"fig5.width{row['n']}", 0.0,
                 f"direct_coll={row['direct_coll_bytes_per_chip']}"
                 f";batch_sharded_coll={row['batch_sharded_coll_bytes_per_chip']}"
                 f";gemm_coll={row['gemm_coll_bytes_per_chip']}")

    if os.path.isdir(args.artifacts):
        from .roofline import roofline_table
        for r in roofline_table(args.artifacts):
            if not r or r.get("skipped") or "error" in r:
                continue
            emit(f"roofline.{r['arch']}.{r['shape']}", 0.0,
                 f"frac={r['roofline_fraction']:.2f};dom={r['dominant']}")


if __name__ == "__main__":
    main()
