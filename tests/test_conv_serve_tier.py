"""The serving tier (DESIGN.md §15): Co-block model-axis sharding is
bit-identical to single-device, the bucketer's pad/slice round-trips, the
slot pool's release/occupancy accounting is exact under a deterministic
arrival trace, and ragged mixed-size traffic serves end-to-end through
``ConvServer`` — plus the ``ConvContext`` unification the tier keys on.

Mesh-dependent cases run in a subprocess (the host-device-count env var
must be set before jax initializes), same pattern as
``tests/test_conv_sharded.py``; the scheduler/bucketer/context cases are
pure host logic and run in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# f32 sharded-vs-single-device tolerance on the CPU backend.  XLA:CPU picks
# the summation blocking of a contraction from its operand extents, and a
# shard's extents differ from the whole batch's (n/data rows, co/model
# channels) — in the jnp oracle's convs, in the interpret-mode kernel
# bodies and in the head matmul alike — so the logits agree to a few f32
# ulps (measured: 6e-8 absolute, 2e-6 relative), not bit for bit.  bf16
# rounding absorbs the difference and stays exact below; on the chip the
# per-shard Pallas program is the single-device program (chip_smoke.py
# --chips 4 compares them).
SHARD_TOL = dict(rtol=1e-5, atol=1e-7)


def run_probe(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from repro.launch.conv_serve import (ConvServer,
                                             make_sharded_cnn_forward,
                                             sharded_cnn_predict)
        from repro.nn.conv import BlockedCNN, BlockedConv2D
        from repro.nn.module import init_tree
        from repro.serve import ConvRequest
        # co=16/32 with lane-8 pencils: a model axis of 2 keeps whole
        # 8-pencil Co blocks per shard (co_shard_convs' invariant)
        model = BlockedCNN(convs=(
            BlockedConv2D(ci=8, co=16, lane=8),
            BlockedConv2D(ci=16, co=16, stride=2, lane=8, hob=3, wob=6),
            BlockedConv2D(ci=16, co=32, lane=8)), n_classes=5)
        p = init_tree(model.specs(), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 12, 12, 8)).astype(np.float32))
        mesh = make_test_mesh(data=4, model=2)
    """) + f"TOL = {SHARD_TOL!r}\n" + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# Co-block model-axis sharding: bit-identical to single-device
# ---------------------------------------------------------------------------

def test_co_sharded_forward_bit_identical_f32():
    """Weights shard on their leading Co/Cob dim, each shard runs the
    unmodified blocked kernel over co/M channels, one all_gather per layer
    boundary — and the logits match single-device (to ``SHARD_TOL``: the
    CPU backend's contractions are not shape-invariant)."""
    run_probe("""
f = make_sharded_cnn_forward(model, mesh, "data", model_axis="model")
got = np.asarray(f(p, x))
want = np.asarray(model(p, x))
np.testing.assert_allclose(got, want, **TOL)
print("OK")
""")


def test_co_sharded_forward_bit_identical_bf16():
    """Same invariant under the bf16 precision policy, through the one
    ConvContext object: bf16 operands chain between sharded layers exactly
    as they do on one device."""
    run_probe("""
from repro.core.context import ConvContext
ctx = ConvContext(precision="bf16")
f = make_sharded_cnn_forward(model, mesh, "data", model_axis="model",
                             context=ctx)
got = np.asarray(f(p, x))
want = np.asarray(model(p, x, context=ctx))
assert got.dtype == want.dtype
np.testing.assert_array_equal(got, want)
print("OK")
""")


def test_co_sharded_pallas_path_bit_identical():
    run_probe("""
from repro.core.context import ConvContext
ctx = ConvContext(impl="window", interpret=True)
f = make_sharded_cnn_forward(model, mesh, "data", model_axis="model",
                             context=ctx)
got = np.asarray(f(p, x))
want = np.asarray(model(p, x, context=ctx))
np.testing.assert_allclose(got, want, **TOL)
print("OK")
""")


def test_co_shard_rejects_pencil_breaking_width():
    """co=24 over m=2 would pick a 6-pencil where the full layout picks 8 —
    shard boundaries would not be weight-block boundaries; must refuse."""
    from repro.launch.conv_serve import co_shard_convs
    from repro.nn.conv import BlockedCNN, BlockedConv2D

    bad = BlockedCNN(convs=(BlockedConv2D(ci=8, co=24, lane=8),),
                     n_classes=3)
    with pytest.raises(ValueError, match="pencil"):
        co_shard_convs(bad, 2)
    grouped = BlockedCNN(convs=(
        BlockedConv2D(ci=8, co=16, lane=8, groups=2),), n_classes=3)
    with pytest.raises(ValueError, match="dense-only"):
        co_shard_convs(grouped, 2)


def test_per_shard_dispatch_key():
    """DispatchKey.shard: batch over data, Co over model; spatial extents,
    dtype, direction and fusion unchanged."""
    from repro.core.dispatch import DispatchKey

    key = DispatchKey.make(8, 12, 12, 8, 32, 3, 3, 1, "SAME", "bf16")
    shard = key.shard(data=4, model=2)
    assert (shard.n, shard.co) == (2, 16)
    assert (shard.hi, shard.wi, shard.ci) == (12, 12, 8)
    assert shard.dtype == "bf16" and shard.direction == "fwd"
    with pytest.raises(ValueError, match="divide"):
        key.shard(model=3)
    grouped = DispatchKey.make(8, 12, 12, 8, 8, 3, 3, groups=2)
    with pytest.raises(ValueError, match="dense-only"):
        grouped.shard(model=2)


# ---------------------------------------------------------------------------
# Bucketer: pad/slice round-trip
# ---------------------------------------------------------------------------

def test_bucketer_pad_crop_round_trip():
    from repro.serve import SpatialBucketer

    b = SpatialBucketer([(16, 16), (8, 8), (12, 16)])
    assert b.buckets == ((8, 8), (12, 16), (16, 16))
    rng = np.random.default_rng(0)
    for h, w in [(5, 7), (8, 8), (9, 13), (12, 16), (16, 16), (1, 1)]:
        img = rng.normal(size=(h, w, 3)).astype(np.float32)
        bucket = b.bucket_for(h, w)
        padded = b.pad(img, bucket)
        assert padded.shape == bucket + (3,)
        np.testing.assert_array_equal(b.crop(padded, h, w), img)
        # padding is zeros, bottom/right only
        assert np.all(padded[h:] == 0) and np.all(padded[:, w:] == 0)


def test_bucketer_picks_smallest_fitting_bucket():
    from repro.serve import SpatialBucketer

    b = SpatialBucketer([(8, 8), (12, 16), (16, 16)])
    assert b.bucket_for(5, 5) == (8, 8)
    assert b.bucket_for(9, 13) == (12, 16)   # 192 < 256: least padded area
    assert b.bucket_for(13, 13) == (16, 16)
    with pytest.raises(ValueError, match="exceeds every bucket"):
        b.bucket_for(17, 4)


# ---------------------------------------------------------------------------
# Slot pool: release + occupancy accounting under a deterministic trace
# ---------------------------------------------------------------------------

def test_slot_pool_admission_and_occupancy():
    from repro.serve import ConvRequest, SlotPool

    buckets = [(8, 8), (16, 16)]
    pool = SlotPool(buckets, batch=4)

    def req(rid, bucket):
        r = ConvRequest(rid=rid, image=np.zeros((4, 4, 1), np.float32))
        r.bucket = bucket
        return r

    # deterministic arrival trace: 6 small + 1 big, then 2 more small
    for i in range(6):
        pool.enqueue(req(i, (8, 8)))
    pool.enqueue(req(6, (16, 16)))
    assert pool.admit() == 5                 # 4 small slots + 1 big slot
    assert pool.pending == 7                 # nothing drained yet

    step1 = pool.drain((8, 8))               # full batch: occupancy 1.0
    assert [r.rid for r in step1] == [0, 1, 2, 3]
    assert pool.occupancy((8, 8)) == 1.0

    assert pool.admit() == 2                 # freed slots refill mid-flight
    pool.enqueue(req(7, (8, 8)))
    pool.enqueue(req(8, (8, 8)))
    assert pool.admit() == 2                 # continuous admission
    step2 = pool.drain((8, 8))               # 4/4 again
    assert [r.rid for r in step2] == [4, 5, 7, 8]

    step3 = pool.drain((16, 16))             # 1/4
    assert [r.rid for r in step3] == [6]
    assert pool.occupancy((16, 16)) == 0.25
    assert pool.occupancy() == pytest.approx((1.0 + 1.0 + 0.25) / 3)
    assert pool.pending == 0
    assert pool.drain((8, 8)) == []          # empty drain: no sample
    assert pool.occupancy() == pytest.approx((1.0 + 1.0 + 0.25) / 3)


# ---------------------------------------------------------------------------
# Ragged mixed-size traffic end-to-end through ConvServer
# ---------------------------------------------------------------------------

def test_conv_server_ragged_end_to_end():
    """Mixed-size requests bucket, pad, batch, shard over (data x model),
    and every completed request's logits equal the direct single-device
    forward of its padded image (row-independence of the batch)."""
    run_probe("""
t = [0.0]
def clock():
    t[0] += 1.0
    return t[0]
# bucket-agnostic model: no pinned hob/wob (those must divide the output
# extents, which vary per bucket — the analytical blocking model adapts)
model = BlockedCNN(convs=(
    BlockedConv2D(ci=8, co=16, lane=8),
    BlockedConv2D(ci=16, co=16, stride=2, lane=8),
    BlockedConv2D(ci=16, co=32, lane=8)), n_classes=5)
p = init_tree(model.specs(), jax.random.PRNGKey(0))
srv = ConvServer(model, p, mesh, buckets=[(8, 8), (12, 12)], batch=4,
                 model_axis="model", clock=clock)
sizes = [(8, 8), (6, 7), (12, 12), (10, 9), (8, 8), (11, 12), (5, 5), (3, 12)]
reqs = []
for i, (h, w) in enumerate(sizes):
    r = ConvRequest(rid=i,
                    image=rng.normal(size=(h, w, 8)).astype(np.float32))
    reqs.append(r)
    srv.submit(r)
done = srv.run()
assert sorted(r.rid for r in done) == list(range(len(sizes))), done
assert all(r.done for r in done)
assert 0 < srv.occupancy() <= 1.0
lats = srv.latencies()
assert len(lats) == len(sizes) and (lats > 0).all()
for r in done:
    img = srv.bucketer.pad(r.image, r.bucket)
    want = np.asarray(model(p, img[None]))[0]
    np.testing.assert_array_equal(r.logits, want)
print("OK")
""")


def test_sharded_predict_degenerate_batch_routes_single_device():
    """pad >= n (tiny ragged batch on a wide data axis) must skip the
    sharded path — and still match the single-device forward exactly; the
    sharded ragged batch matches it to ``SHARD_TOL``."""
    run_probe("""
calls = {"n": 0}
import repro.launch.conv_serve as CS
orig = CS.make_sharded_cnn_forward
def counting(*a, **k):
    calls["n"] += 1
    return orig(*a, **k)
CS.make_sharded_cnn_forward = counting
got = np.asarray(sharded_cnn_predict(model, p, x[:1], mesh))
np.testing.assert_array_equal(got, np.asarray(model(p, x[:1])))
assert calls["n"] == 0, "degenerate batch must not take the sharded path"
got3 = np.asarray(CS.sharded_cnn_predict(model, p, x[:3], mesh,
                                         model_axis="model"))
np.testing.assert_allclose(got3, np.asarray(model(p, x[:3])), **TOL)
assert calls["n"] == 1, "non-degenerate ragged batch shards"
print("OK")
""")


# ---------------------------------------------------------------------------
# ConvContext: the one execution-context object
# ---------------------------------------------------------------------------

def test_conv_context_normalizes_and_hashes_equal():
    from repro.core.context import ConvContext
    from repro.core.dispatch import Impl

    a = ConvContext(impl="jnp", precision="bf16")
    b = ConvContext(impl=Impl.JNP, precision="bf16")
    assert a == b and hash(a) == hash(b)
    assert a.impl is Impl.JNP
    assert a.resolve_precision_for("f32").name == "bf16"
    assert ConvContext().resolve_precision_for("f32").name == "f32"


def test_legacy_kwargs_rejected_by_name():
    """The deprecation shim is gone (ISSUE 10): every conv entry point
    rejects the loose kwargs with a TypeError that names ConvContext."""
    import jax

    from repro.kernels import ops
    from repro.nn.conv import BlockedCNN, BlockedConv2D
    from repro.nn.module import init_tree
    from repro.train.trainstep import TrainSettings

    assert not hasattr(__import__("repro.core.context", fromlist=["x"]),
                       "resolve_context")
    model = BlockedCNN(convs=(BlockedConv2D(ci=8, co=16, lane=8),),
                       n_classes=3)
    p = init_tree(model.specs(), jax.random.PRNGKey(0))
    x = np.zeros((2, 8, 8, 8), np.float32)
    w = np.zeros((3, 3, 8, 16), np.float32)
    for call in (lambda: model(p, x, impl="jnp"),
                 lambda: ops.direct_conv2d(x, w, impl="jnp", interpret=True),
                 lambda: TrainSettings(impl="window"),
                 lambda: TrainSettings(dispatch=None, precision="bf16")):
        with pytest.raises(TypeError, match="ConvContext"):
            call()


def test_context_spelling_matches_direct_math():
    """The one context spelling reproduces the reference math exactly."""
    import jax
    import jax.numpy as jnp

    from repro.core.context import ConvContext
    from repro.core.direct_conv import direct_conv_nhwc
    from repro.nn.conv import BlockedCNN, BlockedConv2D
    from repro.nn.module import init_tree

    model = BlockedCNN(convs=(BlockedConv2D(ci=8, co=16, lane=8),),
                       n_classes=3)
    p = init_tree(model.specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 8, 8, 8)).astype(np.float32)

    got = np.asarray(
        model(p, x, context=ConvContext(impl="jnp", precision="bf16")))
    want = np.asarray(model(p, x, context=ConvContext(impl="jnp")))
    assert str(got.dtype) == "bfloat16" and got.shape == (2, 3)
    np.testing.assert_allclose(np.float32(got), want, rtol=0, atol=5e-2)


def test_sharded_forward_cache_keys_on_context():
    run_probe("""
from repro.core.context import ConvContext
f1 = make_sharded_cnn_forward(model, mesh, "data",
                              context=ConvContext(impl="jnp"))
f2 = make_sharded_cnn_forward(model, mesh, "data",
                              context=ConvContext(impl="jnp"))
assert f1 is f2, "equal contexts must share one cache entry"
f3 = make_sharded_cnn_forward(model, mesh, "data",
                              context=ConvContext(impl="window",
                                                  interpret=True))
assert f3 is not f1
print("OK")
""")
