"""Share of the traced window's wall time, percent, that the consumer
thread (the one that dispatches the steps) spent in ``prefetch.get_wait``:
starved by its producer. 0 where it never waited."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    consumer = program_spans.consumer_thread(events)
    wall = ctx.facts.get("window_s")
    if consumer is None or not wall:
        return None
    waited = sum(e.get("dur", 0.0) / 1e6
                 for e in program_spans.named(events, "prefetch.get_wait")
                 if e["tid"] == consumer)
    return 100.0 * waited / wall
