"""XLA backend compiles inside the window, from jax.monitoring; expected 0."""


def read(run):
    return run["window"]["window_compiles"]
