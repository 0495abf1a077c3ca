"""Median time of one serving step's host-to-device copy, in ms: the
program's ``serve.h2d`` span (the staged batch put on the forward's input
sharding, waited for) over the steps of the traced window.  Moves
serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.h2d")
