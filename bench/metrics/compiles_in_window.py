"""Engine host loop: backend compiles and persistent-cache hits that JAX
reported (``jax.monitoring``) while the window was open."""


def read(run):
    return float(run.compiles_in_window)
