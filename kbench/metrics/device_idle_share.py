"""The device's idle share of the traced window, in %: 1 - the union of
its operations' intervals in the profiler trace over the window."""


def read(ctx):
    if ctx["trace"] is None or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["window_s"])
