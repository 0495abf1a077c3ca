"""Median latency of every request due in the traced run's window, each from
its due time in the open-loop schedule to its logits on the host.  Per layer
and without a bound: the host sets it, and across runs it spreads too widely
for any bound a check can hold (PERF.md).  Moves serve_images_per_s."""


def read(run):
    return run.e2e.get("serve_p50_ms")
