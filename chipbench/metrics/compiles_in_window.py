"""jit: XLA programs built inside the measured window, by compiling or by
loading from the persistent cache (JAX's ``backend_compile_duration``
events wrap both); should be 0."""


def read(ctx):
    return float(ctx["compiles"])
