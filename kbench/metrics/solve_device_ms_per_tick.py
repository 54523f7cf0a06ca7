"""Device milliseconds of the incremental session's executables per
tick: their ``XLA Modules`` time in the profiler trace (the solve, in
its pallas or XLA form, and the dirty-row scatter) over the ticks."""

from kbench.trace import module_seconds

SESSION_EXECUTABLES = (
    "_solve_packed", "_solve_with_state_xla", "_scatter_rows",
    "solve_waves_with_state", "solve_sinkhorn_with_state",
)


def read(ctx):
    ticks = ctx["phases"].get("solve", (0, 0.0))[0]
    if ctx["trace"] is None or not ticks:
        return None
    sec = module_seconds(ctx["trace"], SESSION_EXECUTABLES)
    return 1e3 * sec / ticks if sec > 0 else None
