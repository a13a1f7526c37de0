"""The ``train_rate`` the host alone would allow, rows a second: the rows
of a micro-batch over the larger of what it costs the producer side (mean
``prefetch.pull`` + ``ftrl.encode`` + ``ftrl.ship``) and the consumer side
(lower quartile of ``ftrl.batch``: the iterations that did not wait for
the device). ``host_ceiling / train_rate`` is the step speed-up beyond
which the cell turns host-paced."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    producer = program_spans.producer_seconds(events)
    consumer = program_spans.lower_quartile(
        program_spans.seconds(events, "ftrl.batch"))
    if producer is None or consumer is None:
        return None
    slower = max(producer, consumer)
    return ctx.facts["batch_rows"] / slower if slower > 0 else None
