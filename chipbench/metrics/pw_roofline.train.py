"""Share of its roofline that the pointwise kernel family
(``kernels/conv2d_pointwise``) reached in the traced window, in percent
(``counts.roofline_share``).  Moves train_step_ms."""
from chipbench import counts


def read(run):
    return counts.roofline_share(run, "pointwise")
