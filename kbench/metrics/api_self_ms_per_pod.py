"""Self milliseconds of the API front door per pod bound: the window's
``api.request`` phase time (every non-watch HTTP request, decode
through encode) less its ``store.commit`` time (every store write, each
inside the request that made it), over the pods bound in the window."""


def read(ctx):
    n, api_s = ctx["phases"].get("api.request", (0, 0.0))
    if not n or not ctx["scheduled"]:
        return None
    store_s = ctx["phases"].get("store.commit", (0, 0.0))[1]
    return 1e3 * (api_s - store_s) / ctx["scheduled"]
