"""Share of a fit, percent, that goes to choosing the initial centroids:
the program's ``kmeans.init`` (the k-means|| program and its fetch) and
``kmeans.recluster`` (the host's weighted k-means++) spans over its
``kmeans.fit`` spans, summed over the traced window."""

from benchmark import program_spans


def read(ctx):
    events = program_spans.window_events()
    whole = sum(program_spans.seconds(events, "kmeans.fit"))
    if whole <= 0:
        return None
    init = sum(sum(program_spans.seconds(events, name))
               for name in ("kmeans.init", "kmeans.recluster"))
    return 100.0 * init / whole
