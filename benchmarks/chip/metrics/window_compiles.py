"""Engine (``run_continuous``): XLA compilations inside the measured window
(``/jax/core/compile/backend_compile_duration`` events, persistent-cache
loads included).  Moves ``itl_p95_ms``."""


def read(ctx):
    return ctx.window_compiles
