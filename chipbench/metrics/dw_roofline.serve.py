"""Share of its roofline that the depthwise kernel family
(``kernels/conv2d_depthwise``) reached in the traced window, in percent
(``counts.roofline_share``).  Moves serve_images_per_s."""
from chipbench import counts


def read(run):
    return counts.roofline_share(run, "depthwise")
