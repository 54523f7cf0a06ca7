"""Pods the scheduler bound per tick over the traced window: the delta
of ``scheduler_pods_scheduled_total{result="scheduled"}`` over the
ticks (``solve`` phase observations)."""


def read(ctx):
    ticks = ctx["phases"].get("solve", (0, 0.0))[0]
    if not ticks:
        return None
    return ctx["scheduled"] / ticks
