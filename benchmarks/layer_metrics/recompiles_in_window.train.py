"""Compilations JAX made inside the window: the program's own ``recompile``
counter (FusedTrainStep) plus JAX's compile events (plain ``jax.jit``)."""


def read(ctx):
    return ctx["recompiles"] + ctx["counters"].get("recompile", 0)
