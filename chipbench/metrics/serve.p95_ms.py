"""95th percentile latency of every request due in the traced run's window,
timed as ``serve.p50_ms`` is.  Per layer and without a bound: across runs on
one chip it spreads too widely for any bound a check can hold (PERF.md).
Moves serve_images_per_s."""


def read(run):
    return run.e2e.get("serve_p95_ms")
