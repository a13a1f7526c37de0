"""The due-time 95th percentile above the knee: recorded, not judged."""


def read(ctx):
    return ctx.facts.get("p95_ms")
