"""Rows per dispatch of the server's micro-batcher over the window, from
``PredictServer.stats()``."""


def read(ctx):
    n = ctx.facts.get("dispatches")
    return ctx.facts["rows_dispatched"] / n if n else None
