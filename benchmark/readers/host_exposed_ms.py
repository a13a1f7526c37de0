"""Host milliseconds of a fit during which the program did not wait for
the device: in the traced window, a root ``*.fit`` span less the
``comqueue.wait`` spans under it, mean over the fits. What is left is
the host between and around the programs (prepare, dispatch, fetches,
the model's conversion), whether or not the device still had work; a
fetch that waits on a short device program counts here. A program
without ``comqueue.wait`` gives nothing to read."""

from benchmark import setup_spans


def read(ctx):
    events = setup_spans.in_window()
    if not events or not any(e["name"] == setup_spans.WAIT for e in events):
        return None
    fits = setup_spans.fit_less(events, (setup_spans.WAIT,))
    if not fits:
        return None
    return sum(fits) / len(fits) * 1e3
