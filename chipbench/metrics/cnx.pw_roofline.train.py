"""Share of its roofline that the pointwise kernel family
(``kernels/conv2d_pointwise``, by the names in ``kernels.json``) reached in
the traced window of a ConvNeXt training run, in percent: the least time
of the blocks' two linear layers in forward, input and weight gradient, at
published widths (``families/convnext_counts``), times the window's steps,
over the family's device time.  Moves train_step_ms."""
from chipbench.families import convnext_counts


def read(run):
    if run.trace is None:
        return None
    f = run.trace.families.get("pointwise") or {}
    return convnext_counts.roofline_share(run, "pointwise", f.get("seconds"))
