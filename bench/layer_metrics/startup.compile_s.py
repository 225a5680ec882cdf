"""Seconds JAX spent tracing, lowering and compiling (or loading from
the persistent cache) during set-up, from ``jax.monitoring``'s compile
duration events."""


def read(run):
    return run.setup_compile_s
