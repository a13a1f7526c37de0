"""Seconds of set-up (the boot's end to the window's start) inside JAX's
backend compile, from the ring's ``jit.compile`` events: XLA's compile
where the persistent cache missed, the retrieval where it hit."""

from benchmark import setup_spans


def read(ctx):
    setup = setup_spans.before_window(ctx)
    if setup is None:
        return None
    return setup.jit_seconds(setup_spans.JIT_COMPILE)
