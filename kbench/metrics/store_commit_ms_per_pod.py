"""Milliseconds of store writes per pod bound: the window's
``store.commit`` phase time (lock wait, apply, WAL append and the
durability ack of each write call) over the pods bound in the window."""


def read(ctx):
    n, sec = ctx["phases"].get("store.commit", (0, 0.0))
    if not n or not ctx["scheduled"]:
        return None
    return 1e3 * sec / ctx["scheduled"]
