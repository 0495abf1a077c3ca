import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first initialization.  (Smoke tests and benches see 1 device —
# this env var is set here only, never globally.)

import argparse
import json
import time
import traceback

import jax

from repro.configs.registry import list_archs
from repro.configs.shapes import SHAPES
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.nn.module import Parallelism
from repro.utils.hlo import collective_bytes


def mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def artifact_path(outdir: str, arch: str, shape: str, multi_pod: bool) -> str:
    return os.path.join(outdir, f"{arch}__{shape}__{mesh_tag(multi_pod)}.json")


def refresh_unrolled(arch: str, shape_name: str, outdir: str) -> dict:
    """Recompute only the unrolled cost section of an existing artifact."""
    path = artifact_path(outdir, arch, shape_name, False)
    with open(path) as f:
        record = json.load(f)
    if record.get("skipped") or "error" in record:
        return record
    from repro.train.trainstep import TrainSettings
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=False)
        px = Parallelism(mesh=mesh)
        su = TrainSettings(remat="full", chunk=2048, accum_steps=1,
                           unroll=True)
        cell_u = build_cell(arch, shape_name, px, settings=su)
        compiled_u = cell_u.lower().compile()
        txt_u = compiled_u.as_text()
        record["unrolled"] = {
            "compile_s": round(time.time() - t0, 2),
            "cost_analysis": {
                k: float(v) for k, v in
                compiled_u.cost_analysis().items()
                if isinstance(v, (int, float))
                and not any(ch.isdigit() for ch in k)},
            "collectives": collective_bytes(txt_u),
        }
        del compiled_u, txt_u
    except Exception as e:
        record["unrolled_refresh_error"] = f"{type(e).__name__}: {e}"
    with open(path + ".tmp", "w") as f:
        json.dump(record, f, indent=1)
    os.replace(path + ".tmp", path)
    print(f"[dryrun] refresh-unrolled {arch} x {shape_name}: "
          f"{round(time.time() - t0, 1)}s", flush=True)
    return record


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             skip_existing: bool = True) -> dict:
    path = artifact_path(outdir, arch, shape_name, multi_pod)
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    os.makedirs(outdir, exist_ok=True)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag(multi_pod),
              "n_devices": len(jax.devices())}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        px = Parallelism(mesh=mesh)
        cell = build_cell(arch, shape_name, px)
        if cell.skipped:
            record.update(skipped=True, reason=cell.skipped)
        else:
            t_lower0 = time.time()
            lowered = cell.lower()
            t_lower = time.time() - t_lower0
            t_comp0 = time.time()
            compiled = lowered.compile()
            t_comp = time.time() - t_comp0

            ca = compiled.cost_analysis()
            ma = compiled.memory_analysis()
            txt = compiled.as_text()
            coll = collective_bytes(txt)

            cfg = cell.cfg
            record.update(
                skipped=False,
                lower_s=round(t_lower, 2), compile_s=round(t_comp, 2),
                cost_analysis={k: float(v) for k, v in ca.items()
                               if isinstance(v, (int, float))
                               and not any(ch.isdigit() for ch in k)},
                memory_analysis={
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                    "generated_code_bytes": int(ma.generated_code_size_in_bytes),
                },
                collectives=coll,
                n_params=int(cfg.n_params()),
                n_active_params=int(cfg.n_active_params()),
                seq_len=cell.shape.seq_len,
                global_batch=cell.shape.global_batch,
                kind=cell.shape.kind,
                hlo_ops={"n_lines": txt.count("\n")},
            )
            del compiled, lowered, txt

            if not multi_pod:
                # Second pass with the layer scan UNROLLED: XLA cost_analysis
                # counts while-bodies once, so true per-step FLOPs/bytes and
                # collective traffic come from the unrolled module (the
                # scanned pass above provides memory + shardability).
                # accum_steps=1 so the whole step's work is visible (the
                # accumulation loop is also a while op); memory feasibility
                # was already proven by the scanned pass above.
                from repro.train.trainstep import TrainSettings
                su = TrainSettings(remat="full", chunk=2048, accum_steps=1,
                                   unroll=True)
                cell_u = build_cell(arch, shape_name, px, settings=su)
                t0u = time.time()
                compiled_u = cell_u.lower().compile()
                txt_u = compiled_u.as_text()
                record["unrolled"] = {
                    "compile_s": round(time.time() - t0u, 2),
                    "cost_analysis": {
                        k: float(v) for k, v in
                        compiled_u.cost_analysis().items()
                        if isinstance(v, (int, float))
                        and not any(ch.isdigit() for ch in k)},
                    "collectives": collective_bytes(txt_u),
                }
                del compiled_u, txt_u
    except Exception as e:  # record failures as artifacts too
        record.update(skipped=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    record["wall_s"] = round(time.time() - t0, 2)
    with open(path + ".tmp", "w") as f:
        json.dump(record, f, indent=1)
    os.replace(path + ".tmp", path)
    status = ("SKIP" if record.get("skipped") else
              "FAIL" if "error" in record else "OK")
    print(f"[dryrun] {arch} x {shape_name} x {mesh_tag(multi_pod)}: {status} "
          f"({record['wall_s']}s)", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--unroll-only", action="store_true",
                    help="refresh the unrolled cost section of existing "
                         "single-pod artifacts (attention-scan fix)")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    if args.unroll_only:
        for arch in archs:
            for shape in shapes:
                refresh_unrolled(arch, shape, args.out)
        print("[dryrun] unroll refresh done")
        raise SystemExit(0)
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, multi, args.out,
                               skip_existing=not args.force)
                if "error" in rec:
                    failures += 1
    print(f"[dryrun] done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
