"""Rows a second the program's host encode sustains: rows of the traced
window's micro-batches over the summed seconds of their ``ftrl.encode``
spans (parse, pad and lay out, on the prefetch thread)."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    busy = sum(program_spans.seconds(events, "ftrl.encode"))
    if busy <= 0:
        return None
    return program_spans.rows_encoded(events, ctx.facts["batch_rows"]) / busy
