"""decode_roofline: the LLM's decode step's least time on this chip
(`ops.jamba.step_cost` at each call's padded rows: the larger of its
operations over peak FLOP/s and its bytes over peak bandwidth, which at
these rows is the bytes: every weight and the Mamba state once a step),
averaged over the window's steps, over the device time of one
``jit_decode_step`` execution in the trace.  Both sides are per step,
so executions the trace did not keep move neither."""
from harness.peaks import peaks
from ops.cascade import least_seconds
from ops.jamba import step_cost


def read(ctx):
    p = (ctx.get("trace") or {}).get("programs", {}).get("jit_decode_step")
    calls = ctx.get("decode_calls")
    if not p or not p[0] or not calls:
        return None
    peak = peaks(ctx["device_kind"])
    steps = sum(s for _, s in calls)
    least = sum(s * least_seconds(step_cost(rows, ctx["llm"],
                                            ctx["context"]), peak)
                for rows, s in calls) / steps
    return 100.0 * least / (p[0] / p[1])
