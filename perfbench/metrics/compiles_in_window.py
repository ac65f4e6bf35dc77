"""device: XLA backend compiles inside the window (the benchmark's own
jax.monitoring listener; a persistent-cache hit is not a compile)."""


def read(run):
    return run.window.compiles
