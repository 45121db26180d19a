"""generate_ms: mean wall time of the program's ``generate`` span per
window request: the LLM's answers to the request's miss-group leaders
(prefill, decode steps and the per-step copies of the tokens), 0 when
every question hit."""
from harness.stats import mean


def read(ctx):
    spans = ctx["spans"].get("generate")
    return mean(spans) * 1e3 if spans else None
