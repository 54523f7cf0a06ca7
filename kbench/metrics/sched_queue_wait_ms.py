"""Mean milliseconds a pod waited in the scheduler's queue: the
window's ``sched.queue_wait`` phase, one observation per pod from its
(re)queueing to the tick that drained it."""


def read(ctx):
    n, sec = ctx["phases"].get("sched.queue_wait", (0, 0.0))
    if not n:
        return None
    return 1e3 * sec / n
