"""Share of a fit, percent, that goes to reading the table's design and
taking its moments before the first superstep: the program's
``linear.extract`` and ``linear.moments`` spans over its ``linear.fit``
spans, summed over the traced window. A configuration that cuts the
number of supersteps reads a larger share than its deployment would."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    whole = sum(program_spans.seconds(events, "linear.fit"))
    if whole <= 0:
        return None
    before = sum(sum(program_spans.seconds(events, name))
                 for name in ("linear.extract", "linear.moments"))
    return 100.0 * before / whole
