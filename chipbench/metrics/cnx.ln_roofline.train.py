"""Share of its roofline that the LayerNorm kernels
(``kernels/layer_norm``) reached in the traced window of a ConvNeXt
training run, in percent: the least HBM time of every LayerNorm's forward
and backward (``families/convnext_counts``), times the window's steps,
over the device time of the trace's ops named after the two launches,
``layer_norm_blocked_pallas`` and ``layer_norm_bwd_pallas`` (under
``jax.grad`` with prefixes such as ``jvp_jit_``).  Moves train_step_ms."""
from chipbench import trace
from chipbench.families import convnext_counts

NAMES = ("layer_norm_blocked_pallas", "layer_norm_bwd_pallas")


def read(run):
    if run.trace is None:
        return None
    seconds = sum(s for op, s in run.trace.ops.items()
                  if any(n in trace.base_name(op) for n in NAMES))
    return convnext_counts.roofline_share(run, "layer_norm", seconds)
