"""Median host time of one serving step's staging, in ms: the program's
``serve.stage`` span (expiry sweep, admission, drain, pad, stack, zero
fill) over the steps of the traced window.  Moves serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.stage")
