"""Published forward and backward FLOPs per image of a ConvNeXt
configuration (``families/convnext_counts.train_flops``) times images per
second of the window, over the peak of the chips, in percent.  Nothing
recomputed or padded counts; LayerNorm, GELU and the layer scale are not
FLOPs of the published count.  Moves train_step_ms."""
from chipbench.families import convnext_counts


def read(run):
    images, elapsed = run.counters.get("images"), run.counters.get("elapsed_s")
    if not images or not elapsed:
        return None
    peak = run.peak["flops_per_s"][run.cfg["precision"]] * run.cell["chips"]
    return (100.0 * images * convnext_counts.train_flops(run.cfg)
            / elapsed / peak)
