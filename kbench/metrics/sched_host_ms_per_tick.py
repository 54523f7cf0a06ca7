"""Host milliseconds of the scheduler daemon per tick: the deltas over
the traced window of the scheduler's phase spans
(``utils/tracing.PHASE_SECONDS``) for its host phases, over the ticks
(``solve`` observations, one per dispatched tick). ``readback`` is
left out: it blocks on the device and so holds the device's time."""

HOST_PHASES = ("lower", "upload", "solve", "bind", "capacity", "explain")


def read(ctx):
    ticks = ctx["phases"].get("solve", (0, 0.0))[0]
    if not ticks:
        return None
    host = sum(ctx["phases"].get(ph, (0, 0.0))[1] for ph in HOST_PHASES)
    return 1e3 * host / ticks
