"""flush_ms: mean wall time of the program's ``flush`` span, one
demotion flush inside ``commit`` (demote the hot tier's coldest rows,
append them to the warm ring, free the strings of the rows it
overwrote, count the demoted rows), which ends before any IVF
rebuild."""
from harness.stats import mean


def read(ctx):
    spans = ctx["spans"].get("flush")
    return mean(spans) * 1e3 if spans else None
