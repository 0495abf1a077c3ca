"""Jaxpr traces and backend compiles that overlap the traced window
of a serving run, as the program's recorder counts them from
``jax.monitoring``; a warm server reads 0.  Moves serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.compiles(run)
