"""Median time of one serving step's readback, in ms: the program's
``serve.readback`` span (the logits to the host) over the steps of the
traced window.  Moves serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.readback")
