"""Seconds jax spent tracing, lowering and compiling (or loading from the
compile cache) the training step: the summed jax.trace, jax.lower and
jax.compile spans under the trainer's train.compile spans."""
from benchmarks.chip.program_obs import compile_seconds


def read(ctx):
    return compile_seconds("train.compile")
