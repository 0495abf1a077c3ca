"""Published forward FLOPs of the real images of each engine step, summed,
over the summed host time of the benchmark's span around
``ConvServer.step()``, over the peak of the chips in the mesh, in percent.
Padded rows and lanes do not count.  Moves serve_images_per_s."""
from chipbench import counts


def read(run):
    if not run.steps:
        return None
    images = sum(s[2] for s in run.steps)
    busy = sum(s[1] - s[0] for s in run.steps)
    peak = run.peak["flops_per_s"][run.cfg["precision"]] * run.cell["chips"]
    return 100.0 * images * counts.forward_flops(run.cfg) / busy / peak
