"""XLA compiles plus persistent-cache loads during the window (jax
monitoring's backend-compile and cache-hit events)."""


def read(run):
    return float(run.compiles)
