"""decode_step_ms: device time of one LLM decode step: the device
seconds of the ``jit_decode_step`` program in the window over its
executions."""


def read(ctx):
    p = (ctx.get("trace") or {}).get("programs", {}).get("jit_decode_step")
    if not p or not p[1]:
        return None
    return p[0] / p[1] * 1e3
