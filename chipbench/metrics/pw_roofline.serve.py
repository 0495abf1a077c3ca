"""Share of its roofline that the pointwise kernel family
(``kernels/conv2d_pointwise``) reached in the traced window, in percent
(``counts.roofline_share``).  Moves serve_images_per_s."""
from chipbench import counts


def read(run):
    return counts.roofline_share(run, "pointwise")
