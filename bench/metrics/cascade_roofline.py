"""cascade_roofline: the cascade lookup's least time on this chip
(`ops.cascade`: the larger of its operations over peak FLOP/s and its
bytes over peak bandwidth), over its device time in the trace, summed
over the window's calls.

The cascade is the program with the most device time among those the
device ran while a ``plan`` span was open: plan dispatches it and waits
for its verdicts.  Its XLA name alone does not identify it, since JAX
names every jitted `functools.partial` ``jit__unknown``."""
from harness.peaks import peaks
from ops.cascade import cascade_cost, least_seconds


def read(ctx):
    in_plan = (ctx.get("trace") or {}).get("by_span", {}).get("plan")
    if not in_plan:
        return None
    seconds, calls = max(in_plan.values(), key=lambda p: p[0])
    least = least_seconds(cascade_cost(**ctx["cascade"]),
                          peaks(ctx["device_kind"]))
    return 100.0 * calls * least / seconds
