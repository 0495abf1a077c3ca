"""Share of its roofline that the depthwise kernel family
(``kernels/conv2d_depthwise``, by the names in ``kernels.json``) reached in
the traced window of a ConvNeXt training run, in percent: the least time
of the 7x7 depthwise convs in forward, input and weight gradient, at
published widths (``families/convnext_counts``), times the window's steps,
over the family's device time.  Moves train_step_ms."""
from chipbench.families import convnext_counts


def read(run):
    if run.trace is None:
        return None
    f = run.trace.families.get("depthwise") or {}
    return convnext_counts.roofline_share(run, "depthwise", f.get("seconds"))
