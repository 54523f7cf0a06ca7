"""The solve's share of its roofline, in %: the least time the chip
could take for the problem the window solved (kbench/work.py: every
bound pod against every real node; the larger of operations over peak
FLOP/s and bytes over peak bandwidth) over the solve executables'
device time in the trace."""

from kbench import work
from kbench.trace import module_seconds

SOLVE_EXECUTABLES = (
    "_solve_packed", "_solve_with_state_xla",
    "solve_waves_with_state", "solve_sinkhorn_with_state",
)


def read(ctx):
    ticks = ctx["phases"].get("solve", (0, 0.0))[0]
    if ctx["trace"] is None or not ticks or not ctx["scheduled"]:
        return None
    device_s = module_seconds(ctx["trace"], SOLVE_EXECUTABLES)
    if device_s <= 0:
        return None
    ops, nbytes = work.solve_work(
        ctx["scheduled"], ticks, ctx["config"]["nodes"], ctx["services"]
    )
    least, bound = work.least_time(ops, nbytes, ctx["device_kind"])
    ctx["notes"].append(
        f"solve roofline: {ops:.6g} ops, {nbytes:.6g} bytes, least time "
        f"{least:.6g}s ({bound}-bound) over {device_s:.6g}s on the device"
    )
    return 100.0 * least / device_s
