"""embed_ms: mean wall time of the program's ``embed`` span (the
embedder's call, which ends in a host copy of the embeddings) per
window request."""
from harness.stats import mean


def read(ctx):
    spans = ctx["spans"].get("embed")
    return mean(spans) * 1e3 if spans else None
