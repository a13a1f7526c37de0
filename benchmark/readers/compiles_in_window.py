"""Programs compiled between window start and window end: XLA backend
compilations as JAX's own monitoring counted them, whoever asked."""


def read(ctx):
    return ctx.facts.get("compiles_in_window")
