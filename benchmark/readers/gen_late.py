"""How late the benchmark's own sender ran: 95th percentile of actual send
less due send, in milliseconds. A starved sender is not a fast server."""


def read(ctx):
    return ctx.facts.get("gen_late_ms")
