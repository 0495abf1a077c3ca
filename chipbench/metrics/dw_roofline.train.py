"""Share of its roofline that the depthwise kernel family
(``kernels/conv2d_depthwise``) reached in the traced window, in percent
(``counts.roofline_share``).  Moves train_step_ms."""
from chipbench import counts


def read(run):
    return counts.roofline_share(run, "depthwise")
