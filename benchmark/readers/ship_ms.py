"""Mean milliseconds of ``ftrl.ship``: the host-to-device transfer of one
encoded micro-batch as the prefetch thread pays it (its dispatch)."""

import statistics

from benchmark import program_spans


def read(ctx):
    ships = program_spans.seconds(program_spans.window_events(), "ftrl.ship")
    return statistics.fmean(ships) * 1e3 if ships else None
