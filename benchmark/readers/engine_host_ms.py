"""Host milliseconds the BSP engine spends around one queue execution:
its ``comqueue.prepare`` (inputs made ready) and ``comqueue.fetch``
(results brought to the host) spans, summed over the traced window, over
the window's ``comqueue.exec`` spans."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    execs = len(program_spans.named(events, "comqueue.exec"))
    if not execs:
        return None
    host = sum(sum(program_spans.seconds(events, name))
               for name in ("comqueue.prepare", "comqueue.fetch"))
    return host / execs * 1e3
