"""XLA executables built or loaded inside the window, counted from JAX's
own monitoring events (``/jax/core/compile/backend_compile_duration``),
not from the program's trace counters."""


def read(run):
    return run.compiles
