"""Median time of one serving step's forward, in ms: the program's
``serve.forward`` span (the compiled network up to ready logits, across
retries) over the steps of the traced window.  Moves serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.forward")
