"""Quickstart: the paper in one file.

Runs a real CNN convolution layer (AlexNet conv2) through:
  1. the zero-memory-overhead direct convolution (paper Alg. 3),
  2. the Pallas TPU kernel (compiled on a TPU, interpret mode elsewhere)
     with blocked layouts,
  3. the im2col+GEMM and FFT baselines (paper §2),
checks they agree, and prints the per-algorithm time + memory overhead.

Usage:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import conv_baselines as B
from repro.core import direct_conv as D
from repro.core.context import ConvContext
from repro.core.blocking import choose_blocking
from repro.core.memory_model import ConvShape, bytes_overhead
from repro.kernels import ops
from repro.utils.cache import enable_compile_cache


def time_fn(fn, *args, iters=3, warmup=1):
    import time as _t
    import jax as _jax
    jfn = _jax.jit(fn)
    for _ in range(warmup):
        _jax.block_until_ready(jfn(*args))
    ts = []
    for _ in range(iters):
        t0 = _t.perf_counter()
        _jax.block_until_ready(jfn(*args))
        ts.append(_t.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main():
    enable_compile_cache()
    s = ConvShape("alexnet.conv2", n=1, hi=27, wi=27, ci=96, co=256,
                  hf=5, wf=5, pad=2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(s.n, s.hi, s.wi, s.ci)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(s.hf, s.wf, s.ci, s.co)).astype(np.float32))

    print(f"== {s.name}: {s.hi}x{s.wi}x{s.ci} -> {s.ho}x{s.wo}x{s.co}, "
          f"{s.flops() / 1e9:.2f} GFLOP")
    blk = choose_blocking(s.padded_hi, s.padded_wi, s.ci, s.co,
                          s.hf, s.wf, s.stride)
    print(f"analytical blocking (TPU v5e): Cob={blk.cob} Cib={blk.cib} "
          f"tile={blk.hob}x{blk.wob}")

    ref = B.conv_lax(x, w, s.stride, s.pad)
    impls = {
        "direct (paper)": lambda: D.direct_conv_nhwc(x, w, s.stride, s.pad),
        "pallas kernel": lambda: ops.direct_conv2d(
            x, w, s.stride, s.pad, context=ConvContext(impl="window")),
        "im2col+GEMM": lambda: B.conv_im2col(x, w, s.stride, s.pad),
        "FFT": lambda: B.conv_fft(x, w, s.stride, s.pad),
    }
    for name, fn in impls.items():
        out = fn()
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-2, (name, err)
        print(f"  {name:28s} max|err| vs XLA oracle = {err:.2e}")

    print("\n== timing (XLA CPU backend; structure, not TPU absolute perf)")
    for name in ("direct (paper)", "im2col+GEMM", "FFT"):
        t = time_fn(impls[name], iters=3)
        print(f"  {name:28s} {t * 1e3:8.2f} ms")

    print("\n== memory overhead beyond input+weights+output (paper's claim)")
    for algo in ("direct", "im2col", "mec", "fft"):
        mb = bytes_overhead(s, algo) / 2**20
        print(f"  {algo:8s} {mb:10.2f} MiB")


if __name__ == "__main__":
    main()
