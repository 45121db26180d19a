"""commit_p95_ms: 95th percentile of the ``commit`` span over the
window's requests: admissions, the inline demotion flush and, where it
runs inline, the IVF rebuild.  The span is host time plus enqueue:
device work it leaves queued lands in the next request's syncs."""
from harness.stats import percentile


def read(ctx):
    spans = ctx["spans"].get("commit")
    return percentile(spans, 95) * 1e3 if spans else None
