import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# before any jax import (see dryrun.py)

import argparse
import json
import re
from collections import defaultdict


from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.nn.module import Parallelism
from repro.train.trainstep import TrainSettings
from repro.utils.hlo import collective_bytes, parse_shape_bytes

"""Hillclimb diagnosis: rebuild one cell (optionally with experimental
settings / rule overrides), compile, and print the largest collectives and
largest-allocation ops with shapes+dtypes — the 'profile' of the dry-run.

  PYTHONPATH=src python -m repro.launch.inspect_cell \
      --arch gemma2-27b --shape prefill_32k [--fused-loss] [--remat dots] \
      [--rule act_seq=model] [--accum 8] [--unroll]
"""

_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"\b", re.M)


def top_collectives(txt: str, n=25):
    rows = []
    for m in _OP.finditer(txt):
        rows.append((parse_shape_bytes(m.group(2)), m.group(3), m.group(2)[:90],
                     m.group(1)[:40]))
    rows.sort(reverse=True)
    agg = defaultdict(lambda: [0, 0])
    for b, kind, shape, _ in rows:
        key = (kind, shape)
        agg[key][0] += b
        agg[key][1] += 1
    merged = sorted(((v[0], k[0], k[1], v[1]) for k, v in agg.items()),
                    reverse=True)
    return merged[:n]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fused-loss", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--accum", type=int, default=0, help="0 = default")
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--rule", action="append", default=[],
                    help="logical=mesh_axis override, e.g. act_seq=model")
    ap.add_argument("--save-json", default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    px = Parallelism(mesh=mesh)
    for r in args.rule:
        k, _, v = r.partition("=")
        px.rules[k] = None if v in ("", "none", "None") else v

    kind_train = args.shape.startswith("train")
    settings = TrainSettings(
        remat=args.remat, chunk=args.chunk,
        accum_steps=(args.accum or (8 if kind_train else 1)) if not args.unroll
        else (args.accum or 1),
        unroll=args.unroll, fused_loss=args.fused_loss)
    cell = build_cell(args.arch, args.shape, px, settings=settings)
    if cell.skipped:
        print("SKIP:", cell.skipped)
        return
    import time
    t0 = time.time()
    comp = cell.lower().compile()
    print(f"compiled in {time.time() - t0:.1f}s")
    ca = comp.cost_analysis()
    ma = comp.memory_analysis()
    txt = comp.as_text()
    coll = collective_bytes(txt)
    flops = ca.get("flops", 0.0)
    byts = ca.get("bytes accessed", 0.0)
    print(f"flops/chip      {flops:.4e}  -> compute  {flops / 197e12:.3f} s")
    print(f"bytes/chip      {byts:.4e}  -> memory   {byts / 819e9:.3f} s")
    wire = 2 * coll.get("all-reduce", 0) + sum(
        coll.get(k, 0) for k in ("all-gather", "reduce-scatter", "all-to-all",
                                 "collective-permute"))
    print(f"wire bytes/chip {wire:.4e}  -> collect. {wire / 50e9:.3f} s")
    print(f"HBM/chip: args {ma.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temp {ma.temp_size_in_bytes / 2**30:.2f} GiB")
    print("\ntop collectives (bytes_total, kind, shape, count):")
    for b, kind, shape, cnt in top_collectives(txt):
        print(f"  {b / 2**20:10.1f} MiB  {kind:18s} x{cnt:<4d} {shape}")
    if args.save_json:
        os.makedirs(os.path.dirname(args.save_json) or ".", exist_ok=True)
        with open(args.save_json, "w") as f:
            json.dump({"flops": flops, "bytes": byts, "collectives": coll,
                       "temp_bytes": int(ma.temp_size_in_bytes),
                       "arg_bytes": int(ma.argument_size_in_bytes)}, f)


if __name__ == "__main__":
    main()
