"""XLA backend compiles inside the window (JAX's
``/jax/core/compile/backend_compile_duration`` events); set-up warms every
shape, so this should read 0."""


def read(run):
    return run.compiles_in_window
