"""Seconds of set-up (the boot's end to the window's start) that JAX
spent tracing functions and lowering them to MLIR, from the ring's
``jit.trace`` and ``jit.lower`` events, nested ones counted once, a
compile inside a trace left to ``setup_compile_s``: Python work that no
compile cache saves."""

from benchmark import setup_spans


def read(ctx):
    setup = setup_spans.before_window(ctx)
    if setup is None:
        return None
    return setup.jit_seconds(setup_spans.JIT_TRACE,
                             but_not=setup_spans.JIT_COMPILE)
