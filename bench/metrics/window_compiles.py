"""Decode step: programs compiled, or loaded from the persistent cache,
inside the window (JAX's backend-compile events through
``jax.monitoring``).  Should be 0."""


def read(run):
    return float(run.compiles)
