"""The readers of the program's spans inside the cache service and the
embedder, on hand-made span lists with known sums."""
import pytest

from harness import cells
from tiny import ROOT

SPANS = {
    "request": [0.020, 0.022, 0.018, 0.020],
    "embed": [0.007, 0.008, 0.006, 0.007],
    "embed.sync": [0.004, 0.005, 0.004, 0.005],
    "plan": [0.009, 0.010, 0.008, 0.009],
    "plan.sync": [0.0005] * 16,
    "plan.coalesce": [0.001] * 4,
    "commit": [0.002, 0.015, 0.002, 0.003],
    "commit.sync": [0.0002] * 9,
    "flush": [0.003, 0.001],
    "rebuild": [0.008],
}
WANT = {
    "plan_host_ms": (0.036 - 0.008) / 4 * 1e3,    # 7.0
    "embed_host_ms": (0.028 - 0.018) / 4 * 1e3,   # 2.5
    "flush_ms": 2.0,
    "rebuild_ms": 8.0,
    "syncs_per_request": (4 + 16 + 9) / 4,        # 7.25
}
# the spans each reader cannot do without
NEEDS = {
    "plan_host_ms": ["plan", "plan.sync"],
    "embed_host_ms": ["embed", "embed.sync"],
    "flush_ms": ["flush"],
    "rebuild_ms": ["rebuild"],
    "syncs_per_request": ["request"],
}


def _read(name, spans):
    path = ROOT / "bench"
    return cells.load_module("metrics", name, path).read({"spans": spans})


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_its_spans(name):
    assert _read(name, SPANS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name,missing", [(n, s) for n in sorted(NEEDS)
                                          for s in NEEDS[n]])
def test_none_without_its_spans(name, missing):
    """A program without the span (such as one from before the span
    existed) gives no reading, and no error."""
    spans = {k: v for k, v in SPANS.items() if k != missing}
    assert _read(name, spans) is None
    assert _read(name, dict(spans, **{missing: []})) is None


def test_syncs_need_a_sync_span():
    spans = {k: v for k, v in SPANS.items() if not k.endswith(".sync")}
    assert _read("syncs_per_request", spans) is None


def test_the_benchmark_lists_each_reader():
    cell = cells.load(ROOT, "chat.repeat")
    assert set(WANT) <= set(cells.readers(cell))
