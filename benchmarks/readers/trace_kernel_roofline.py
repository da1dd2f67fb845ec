"""A kernel's share of its roofline from the profiler trace.

The least time the chip could take for the work the device answered inside
the traced window (``work.py``: operations and bytes per unit, from shapes;
``peaks/``: the chip's published peaks) over the device time of the
events whose names match the kernel's pattern.

source: {"reader": "trace_kernel_roofline", "events": regex,
         "work": {"ops": fn name in work.py, "bytes": fn name in work.py},
         "units": {"counter": registry counter whose movement inside the
                   traced window counts the units of work answered}}
"""

import re


def read(source: dict, ctx: dict):
    from benchmarks import work

    t, tracer = ctx.get("trace"), ctx.get("tracer")
    if not t or tracer is None or tracer.counters_at_stop is None:
        return None
    pattern = re.compile(source["events"])
    kernel_s = sum(secs for name, (secs, _n) in t["by_name"].items() if pattern.search(name))
    name = source["units"]["counter"]
    units = tracer.counters_at_stop.get(name, 0) - tracer.counters_at_start.get(name, 0)
    if kernel_s <= 0 or units <= 0:
        return None
    ops = units * getattr(work, source["work"]["ops"])()
    nbytes = units * getattr(work, source["work"]["bytes"])()
    least_s, _bound = work.roofline_seconds(ops, nbytes, ctx["peak"]())  # an unknown device is an error
    return 100.0 * least_s / kernel_s
