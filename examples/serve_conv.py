"""Conv serving driver: ragged image requests through the serving tier.

Submits a stream of variable-size images into the bucketed continuous
batcher (``repro.serve.ConvServer``): each request pads up to its
dispatch-tuned (H, W) bucket, batches shard over the mesh's ``data`` axis,
and (with ``--model-shard``) every conv's Co/Cob blocks shard over the
``model`` axis — the paper's §3.2 output-channel parallelism as a mesh
dimension.  Prints per-request latency percentiles and achieved occupancy.

Usage:  python examples/serve_conv.py --requests 24 --batch 4
        python examples/serve_conv.py --model-shard 2
(run from the repo root; the script forces 8 host devices before jax init)
"""
import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--model-shard", type=int, default=1,
                    help="model-axis width (Co-block sharding; 1 = off)")
    args = ap.parse_args()

    import jax
    from repro.launch.mesh import make_serve_mesh
    from repro.nn.conv import BlockedCNN, BlockedConv2D
    from repro.nn.module import init_tree
    from repro.serve import ConvRequest, ConvServer
    from repro.utils.cache import enable_compile_cache

    enable_compile_cache()
    model = BlockedCNN(convs=(
        BlockedConv2D(ci=8, co=16, lane=8),
        BlockedConv2D(ci=16, co=32, stride=2, lane=8),
        BlockedConv2D(ci=32, co=32, lane=8)), n_classes=10)
    params = init_tree(model.specs(), jax.random.PRNGKey(0))
    mesh = make_serve_mesh(model=args.model_shard)
    data = mesh.shape["data"]
    batch = -(-args.batch // data) * data   # slots are data-width multiples
    print(f"mesh: {dict(mesh.shape)}  slots/bucket: {batch}")

    srv = ConvServer(model, params, mesh, buckets=[(16, 16), (24, 24)],
                     batch=batch,
                     model_axis="model" if args.model_shard > 1 else None)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        h, w = int(rng.integers(8, 25)), int(rng.integers(8, 25))
        srv.submit(ConvRequest(
            rid=i, image=rng.normal(size=(h, w, 8)).astype(np.float32)))

    done = srv.run()
    lat = srv.latencies() * 1e3
    print(f"completed {len(done)} requests over "
          f"{sorted({r.bucket for r in done})} buckets")
    print(f"latency p50={np.percentile(lat, 50):.1f}ms "
          f"p99={np.percentile(lat, 99):.1f}ms; "
          f"occupancy={srv.occupancy():.2f}")


if __name__ == "__main__":
    main()
