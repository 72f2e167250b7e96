"""XLA compiles inside the window, counted by a jax.monitoring listener on
the backend-compile event (the persistent cache is off in the window)."""


def read(run):
    return run.compiles
