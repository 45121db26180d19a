"""rebuild_ms: mean wall time of the program's ``rebuild`` span, one
inline k-means re-cluster of the warm IVF, from its dispatch until the
device has finished it."""
from harness.stats import mean


def read(ctx):
    spans = ctx["spans"].get("rebuild")
    return mean(spans) * 1e3 if spans else None
