"""Open-loop image serving through ``ConvServer`` (traffic ``kind: serve``).

Parameters of a mix (``chipbench/traffic/<mix>.json``):

  image_size     square side of every request image (NHWC, float32)
  bucket         the server's one ``(H, W)`` bucket
  batch          the server's batch (rows of one engine step)
  data           data-parallel width of the serving mesh
  rate_per_s     offered load
  pool_images    distinct images, made from the seed in set-up and cycled
  check_sample   served requests compared with the reference per run
  ref_block      rows per block of the reference

Arrivals: ``round(rate * seconds)`` requests whose gaps are the quantiles
of the exponential distribution at that rate -- the gaps of a Poisson
process -- scaled to fill the window; the seed permutes them and draws the
pool image of each request.  So every seed offers the same set of gaps, in
another order.  Each request is timed from its due time to the moment its
logits are back on the host, so a stalled host loop counts against the
requests it delays.  Requests due in the window that are still in flight at
its close are waited for (up to a minute) and count with their whole
latency.  Python's collector is held off over the window, so that no
collection of the set-up's objects lands inside it.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench import harness, trace as tracing

GRACE_S = 60.0


class Setup:
    """The server, its weights and the image pool of one seed."""

    def __init__(self, run: harness.Run, seed: int):
        import jax
        from repro.core.context import ConvContext
        from repro.launch.conv_serve import ConvServer
        from repro.launch.mesh import make_serve_mesh
        t = run.traffic
        fam, ref = harness.family(run.cfg)
        self.ref = ref
        key = ref.key_for(seed)
        self.weights = jax.jit(lambda k: ref.make_weights(run.cfg, k))(
            jax.random.fold_in(key, 0))
        self.model = fam.build(run.cfg)
        params = fam.to_program(run.cfg, self.model, self.weights)
        side = t["image_size"]
        self.pool = np.asarray(jax.jit(lambda k: jax.random.normal(
            k, (t["pool_images"], side, side, run.cfg["in_channels"]),
            np.float32))(jax.random.fold_in(key, 1)))
        mesh = make_serve_mesh(data=t["data"])
        self.server = ConvServer(
            self.model, params, mesh, [tuple(t["bucket"])], t["batch"],
            context=ConvContext(precision=run.cfg["precision"]),
            clock=time.perf_counter)
        self.server.warmup()


def schedule(seed: int, rate: float, seconds: float, pool: int):
    """-> (due times in seconds from the window's start, pool indices)."""
    rng = np.random.default_rng([seed, 2])
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(rng.permutation(gaps)), rng.integers(0, pool, n)


def window(run: harness.Run, st: Setup, seed: int, rate: float,
           seconds: float):
    """Offer the schedule open-loop; -> (requests, t0, due, lateness).

    Fills ``run.steps`` with one ``(t0, t1, real_rows, batch)`` per engine
    step and ``run.counters["degraded"]`` with the ids of requests that a
    step served through the degraded (jnp) executable."""
    from repro.serve.scheduler import ConvRequest
    due, idx = schedule(seed, rate, seconds, len(st.pool))
    server, batch = st.server, run.traffic["batch"]
    reqs = [None] * len(due)
    late = np.zeros(len(due))
    degraded = set()
    n_degraded = server.health()["degraded_steps"]
    run.steps = []
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < len(due) and due[i] <= now:
            r = ConvRequest(rid=i, image=st.pool[idx[i]])
            server.submit(r)
            reqs[i], late[i] = r, now - due[i]
            i += 1
        if server.pool.pending:
            real = min(server.pool.pending, batch)
            done = len(server.completed)
            a = time.perf_counter()
            with run.spans("bench.step"):
                server.step()
            run.steps.append((a, time.perf_counter(), real, batch))
            if server.health()["degraded_steps"] != n_degraded:
                n_degraded = server.health()["degraded_steps"]
                degraded.update(r.rid for r in server.completed[done:])
        elif i < len(due):
            with run.spans("bench.wait_arrival"):
                time.sleep(max(0.0, min(due[i] - now, 0.002)))
        else:
            break
        if now > seconds + GRACE_S:
            break
    run.counters["degraded"] = degraded
    return reqs, t0, due, late


def run(run: harness.Run):
    t = run.traffic
    st = Setup(run, run.seed)
    gc.collect()
    gc.freeze()
    if run.tracing:
        tracer = tracing.Tracer()
        tracer.start()
    t_setup_end = time.perf_counter()
    run.e2e["setup_s"] = t_setup_end - run.t_process
    gc.disable()
    try:
        with run.spans(tracing.WINDOW):
            reqs, t0, due, late = window(run, st, run.seed, t["rate_per_s"],
                                         run.seconds)
    finally:
        gc.enable()
        gc.unfreeze()
    if run.tracing:
        run.trace = tracer.stop(run.spans, harness.kernel_families())
    summarize(run, st, reqs, t0, due, late)
    run.memory_peak_bytes = harness.memory_peak(run.devices)
    check(run, st, reqs, run.seed)


def summarize(run, st, reqs, t0, due, late):
    from repro.serve.scheduler import Outcome
    end = t0 + run.seconds
    ok = [r for r in reqs if r is not None and r.outcome is Outcome.OK]
    lat = np.array([r.t_done - (t0 + due[r.rid]) for r in ok])
    run.attempted = len(reqs)
    run.failed = len(reqs) - len(ok) + len(run.counters["degraded"])
    run.complete = len(ok) == len(reqs)
    if len(lat):
        run.e2e["serve_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
        run.e2e["serve_p95_ms"] = float(np.percentile(lat, 95) * 1e3)
    run.e2e["serve_images_per_s"] = sum(r.t_done <= end for r in ok) / run.seconds
    run.counters["occupancy"] = st.server.occupancy()
    run.counters["health"] = st.server.health()
    print(f"[serve] requests {len(reqs)} ok {len(ok)} steps {len(run.steps)} "
          f"occupancy {run.counters['occupancy']:.4f} generator lateness "
          f"p95 {np.percentile(late, 95) * 1e3:.3f} ms max "
          f"{late.max() * 1e3:.3f} ms", file=sys.stderr, flush=True)
    if run.steps and len(lat):
        dur = np.array([b - a for a, b, _, _ in run.steps]) * 1e3
        rows = np.array([n for _, _, n, _ in run.steps])
        first = due[[r.rid for r in ok]] < run.seconds / 2
        half = [np.percentile(lat[first == h], 50) * 1e3 for h in (1, 0)]
        print(f"[serve] step ms p50 {np.percentile(dur, 50):.3f} p95 "
              f"{np.percentile(dur, 95):.3f} max {dur.max():.3f} rows/step "
              f"{rows.mean():.2f} ms/row {dur.sum() / rows.sum():.4f} "
              f"in steps {dur.sum() / 1e3:.3f} s; latency p50 "
              f"{run.e2e['serve_p50_ms']:.3f} p95 {run.e2e['serve_p95_ms']:.3f}"
              f" ms, p50 of the first half "
              f"{half[0]:.3f} second half {half[1]:.3f} ms",
              file=sys.stderr, flush=True)


def sample(reqs, seed: int, n: int):
    """A seeded sample of the served requests."""
    from repro.serve.scheduler import Outcome
    ok = [r for r in reqs if r is not None and r.outcome is Outcome.OK]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(ok), size=min(n, len(ok)), replace=False)
    return [ok[i] for i in sorted(pick)]


def reference_logits(run, st, images, operand=None):
    """The plain reference over ``images`` in blocks of ``ref_block`` rows."""
    import jax
    import jax.numpy as jnp
    blk = run.traffic["ref_block"]
    f = jax.jit(lambda w, x: st.ref.forward(run.cfg, w, x, operand))
    out = []
    for s in range(0, len(images), blk):
        x = images[s:s + blk]
        n = len(x)
        if n < blk:
            x = np.concatenate([x, np.zeros((blk - n,) + x.shape[1:], x.dtype)])
        with jax.default_matmul_precision("highest"):
            out.append(np.asarray(f(st.weights, jnp.asarray(x)))[:n])
    return np.concatenate(out).astype(np.float64)


def logit_err(got, want) -> float:
    """Worst request's relative L2 gap of its logits."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / np.linalg.norm(want, axis=1)))


def errors(run, st, reqs, seed, controls=()):
    """-> {"program": logit_err, <operand dtype>: logit_err} over a seeded
    sample of the served requests.  For each control operand dtype the
    reference computed at that operand precision stands in for the served
    logits.  The program's state is freed before the reference runs."""
    picked = sample(reqs, seed, run.traffic["check_sample"])
    if not picked:
        return {}
    images = np.stack([r.image for r in picked])
    got = np.stack([np.asarray(r.logits, np.float32) for r in picked])
    st.server = None
    gc.collect()
    want = reference_logits(run, st, images)
    out = {"program": logit_err(got, want)}
    for c in controls:
        out[str(c)] = logit_err(reference_logits(run, st, images, c), want)
    return out


def check(run, st, reqs, seed):
    errs = errors(run, st, reqs, seed)
    if not errs:
        run.complete = False
        return
    run.check("logit_err", errs["program"])
