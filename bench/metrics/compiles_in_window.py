"""JAX backend compilations (compiles and persistent-cache loads) that
finished inside the window, counted by a ``jax.monitoring`` listener.
Warm-up covers every shape the traffic uses, so this should read 0."""


def read(run):
    return run.compiles_in_window
