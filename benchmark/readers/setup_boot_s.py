"""Seconds from the process's start (``ctx.started``: from /proc, the
interpreter's own start-up included) to the end of the process's first
``session.start`` span: interpreter, imports, JAX's backend, device
discovery, the session. The first named part of ``setup_s``."""

from benchmark import setup_spans


def read(ctx):
    setup = setup_spans.before_window(ctx)
    if setup is None:
        return None
    return setup.origin_unix + setup.boot_end - ctx.started
