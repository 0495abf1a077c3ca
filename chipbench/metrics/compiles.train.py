"""Jaxpr traces and backend compiles that overlap the traced window
of a training run, as the program's recorder counts them from
``jax.monitoring``; a warm step reads 0.  Moves train_step_ms."""
from chipbench import program_spans


def read(run):
    return program_spans.compiles(run)
