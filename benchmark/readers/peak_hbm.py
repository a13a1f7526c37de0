"""Peak device memory of the fullest chip after the window, in MB."""


def read(ctx):
    peak = ctx.facts.get("memory_peak_bytes")
    return peak / 1e6 if peak else None
