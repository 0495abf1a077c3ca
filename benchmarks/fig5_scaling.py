"""Paper Fig. 5 (thread scaling), TPU-native form: parallel efficiency of
direct convolution vs GEMM-based convolution as the parallel width grows.

The container has one core, so wall-clock thread scaling is unavailable; the
*structural* reproduction compiles both algorithms sharded over 1..16 devices
(subprocess sets the host-device count) and reports, per width:

  * collective bytes per chip (direct conv over Co: ZERO — the paper's §3.2
    "output channels are embarrassingly parallel"; im2col+GEMM sharded over
    the GEMM K dim: all-reduce traffic growing with width),
  * per-chip FLOPs balance (work divides exactly for direct conv).

This is exactly the mechanism behind the paper's Fig. 5: GEMM-internal
partitioning communicates/skews, Co-parallel direct convolution does not.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os, json, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n)d"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import layout as L
    from repro.core.direct_conv import direct_conv_blocked
    from repro.utils.hlo import collective_bytes

    n = %(n)d
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((n,), ("model",))
    s = dict(hi=30, wi=30, ci=128, co=256, hf=3, wf=3)
    ho = wo = s["hi"] - s["hf"] + 1

    # --- direct conv, blocked layout, sharded over Co blocks (paper §3.2)
    cob = 128 if n <= 2 else s["co"] // n
    xb = jax.ShapeDtypeStruct((1, s["ci"] // 128, s["hi"], s["wi"], 128),
                              jnp.float32)
    wb = jax.ShapeDtypeStruct((s["co"] // cob, s["ci"] // 128, s["hf"],
                               s["wf"], 128, cob), jnp.float32)
    shx = NamedSharding(mesh, P())                      # input replicated
    shw = NamedSharding(mesh, P("model"))               # Co blocks sharded
    f = jax.jit(lambda x, w: direct_conv_blocked(x, w, 1),
                in_shardings=(shx, shw),
                out_shardings=NamedSharding(mesh, P(None, "model")))
    comp = f.lower(xb, wb).compile()
    direct = {
        "collectives": collective_bytes(comp.as_text()),
        "flops": float(comp.cost_analysis().get("flops", 0.0)),
    }

    # --- direct conv, batch sharded via shard_map (the serving arrangement:
    #     repro.launch.conv_serve) — per-shard blocked layouts, and the
    #     forward pass must contain ZERO collectives
    mesh_d = make_mesh_auto((n,), ("data",))
    xb_n = jax.ShapeDtypeStruct((n, s["ci"] // 128, s["hi"], s["wi"], 128),
                                jnp.float32)
    wb_full = jax.ShapeDtypeStruct((s["co"] // 128, s["ci"] // 128, s["hf"],
                                    s["wf"], 128, 128), jnp.float32)
    fb = jax.jit(jax.shard_map(lambda x, w: direct_conv_blocked(x, w, 1),
                               mesh=mesh_d, in_specs=(P("data"), P()),
                               out_specs=P("data"), check_vma=False))
    comp_b = fb.lower(xb_n, wb_full).compile()
    batch_sharded = {
        "collectives": collective_bytes(comp_b.as_text()),
        "flops": float(comp_b.cost_analysis().get("flops", 0.0)),
    }

    # --- im2col+GEMM with the GEMM sharded over K (BLAS-internal style)
    k = s["hf"] * s["wf"] * s["ci"]
    packed = jax.ShapeDtypeStruct((ho * wo, k), jnp.float32)
    wmat = jax.ShapeDtypeStruct((k, s["co"]), jnp.float32)
    g = jax.jit(lambda p, w: p @ w,
                in_shardings=(NamedSharding(mesh, P(None, "model")),
                              NamedSharding(mesh, P("model", None))),
                out_shardings=NamedSharding(mesh, P()))
    comp2 = g.lower(packed, wmat).compile()
    gemm = {
        "collectives": collective_bytes(comp2.as_text()),
        "flops": float(comp2.cost_analysis().get("flops", 0.0)),
    }
    print(json.dumps({"n": n, "direct": direct,
                      "direct_batch_sharded": batch_sharded,
                      "gemm_k_sharded": gemm}))
""")


def bench_fig5(widths=(1, 2, 4, 8, 16)):
    rows = []
    # the children only compile, on virtual CPU devices: pin them to the
    # CPU so they never reach for the chip the parent process holds
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    for n in widths:
        out = subprocess.run([sys.executable, "-c", _SCRIPT % {"n": n}],
                             capture_output=True, text=True, env=env,
                             cwd=REPO, timeout=300)
        if out.returncode != 0:
            rows.append({"n": n, "error": out.stderr[-500:]})
            continue
        r = json.loads(out.stdout.strip().splitlines()[-1])
        rows.append({
            "n": n,
            "direct_coll_bytes_per_chip": r["direct"]["collectives"]["total"],
            "batch_sharded_coll_bytes_per_chip":
                r["direct_batch_sharded"]["collectives"]["total"],
            "gemm_coll_bytes_per_chip": r["gemm_k_sharded"]["collectives"]["total"],
            "direct_flops_per_chip": r["direct"]["flops"],
            "batch_sharded_flops_per_chip": r["direct_batch_sharded"]["flops"],
            "gemm_flops_per_chip": r["gemm_k_sharded"]["flops"],
        })
    return rows
