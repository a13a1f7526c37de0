"""Seconds of set-up inside the trainer's warm fits: the root ``*.fit``
spans that ended before the window, less the ``jit.*`` events under them
(those are ``setup_trace_s`` and ``setup_compile_s``): what a first fit
costs beyond tracing and compiling, the device's work included."""

from benchmark import setup_spans


def read(ctx):
    setup = setup_spans.before_window(ctx)
    if setup is None:
        return None
    fits = setup_spans.fit_less(setup.events, setup_spans.JIT)
    return sum(fits) if fits else None
