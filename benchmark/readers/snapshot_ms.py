"""Mean milliseconds from the last micro-batch of a snapshot cycle being
handed to the trainer to the snapshot being in the consumer's hands."""


def read(ctx):
    s = ctx.facts.get("snapshot_s")
    return sum(s) / len(s) * 1e3 if s else None
