"""Seconds of set-up the FTRL trainer spent on its own state: the self
time (a span less what its children cover, ``jit.*`` events among them)
of ``ftrl.link`` and of ``ftrl.warm_hash``, ``ftrl.state_alloc`` and
``ftrl.state_ship``, before the window."""

from benchmark import setup_spans


def read(ctx):
    setup = setup_spans.before_window(ctx)
    if setup is None:
        return None
    mine = [e for e in setup.events if e["name"] in setup_spans.FTRL_STATE]
    if not mine:
        return None
    return sum(setup_spans.self_seconds(setup.events, e) for e in mine)
