"""The host's own cost of one step dispatch, in milliseconds: the lower
quartile of ``ftrl.dispatch``. After each snapshot the device's queue is
empty and the first dispatches of a cycle return at host speed; the later
ones wait on the runtime's in-flight limit, at the device's pace. So the
durations are two-humped, the lower quartile reads the host and the mean
would read the device."""

from benchmark import program_spans


def read(ctx):
    q1 = program_spans.lower_quartile(
        program_spans.seconds(program_spans.window_events(), "ftrl.dispatch"))
    return q1 * 1e3 if q1 is not None else None
