"""Share of a fit, percent, that goes to binning the raw table (the
quantile edges and the uint8 bins): the program's ``gbdt.bin`` spans over
its ``gbdt.fit`` spans, summed over the traced window. A configuration
that cuts the number of trees reads a larger share than its deployment
would."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    whole = sum(program_spans.seconds(events, "gbdt.fit"))
    if whole <= 0:
        return None
    return 100.0 * sum(program_spans.seconds(events, "gbdt.bin")) / whole
