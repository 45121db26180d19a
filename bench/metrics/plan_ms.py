"""plan_ms: mean wall time of the ``plan`` span (cascade lookup, LRU
touch, answer resolution, admission and miss grouping) per window
request; ``plan`` ends in host copies of the verdicts."""
from harness.stats import mean


def read(ctx):
    spans = ctx["spans"].get("plan")
    return mean(spans) * 1e3 if spans else None
