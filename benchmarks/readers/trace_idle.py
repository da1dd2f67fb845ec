"""Device idle share from the profiler trace: 100 x (1 - union of the
device's operation intervals / traced window), averaged over the chips used.

source: {"reader": "trace_idle"}
"""


def read(source: dict, ctx: dict):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None  # no device event was read: say nothing, never 100
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
