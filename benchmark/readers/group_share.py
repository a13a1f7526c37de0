"""Share of a fit, percent, that goes to grouping the raw triples (both
sides' sorts, offsets and counts): the program's ``als.group`` spans over
its ``als.fit`` spans, summed over the traced window. A configuration that
cuts the number of iterations reads a larger share than its deployment
would."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    whole = sum(program_spans.seconds(events, "als.fit"))
    if whole <= 0:
        return None
    return 100.0 * sum(program_spans.seconds(events, "als.group")) / whole
