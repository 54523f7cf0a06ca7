"""Mean milliseconds a store event waited in the watch fan-out queue:
the window's ``watch.fanout_wait`` phase, one observation per event
from its commit to the dispatcher taking it."""


def read(ctx):
    n, sec = ctx["phases"].get("watch.fanout_wait", (0, 0.0))
    if not n:
        return None
    return 1e3 * sec / n
