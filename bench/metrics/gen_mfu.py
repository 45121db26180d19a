"""gen_mfu: the LLM's model FLOPs (`ops.jamba.generation_flops`) on the
real rows and tokens the window generated, per second of window, as a
share of the chip's bf16 peak.  Padding rows and pad tokens are not
counted."""
from harness.peaks import peaks
from ops.jamba import generation_flops


def read(ctx):
    if not ctx.get("prompt_tokens") or not ctx.get("trace"):
        return None
    flops = generation_flops(ctx["prompt_tokens"], ctx["new_tokens"],
                             ctx["llm"])
    return 100.0 * flops / ctx["window_s"] / peaks(
        ctx["device_kind"])["bf16_flops"]
