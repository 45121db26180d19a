"""The traffic generators: the same seed gives the same stream, another
seed another one, and every seed the same sizes."""
import numpy as np
import pytest

from harness import cells
from tiny import tiny_cell

TEXT = cells.load_module("traffic", "text_queries")
SEEDS = (7, 2 ** 40 + 3)


def _stream(seed, cell):
    t = TEXT.TextTraffic(tiny_cell(cell).traffic, seed)
    pre = t.prefill()
    return pre, t.batches(20), t.probe()


@pytest.mark.parametrize("cell", ["chat.repeat", "chat.single"])
def test_same_seed_same_stream(cell):
    assert _stream(SEEDS[1], cell) == _stream(SEEDS[1], cell)


@pytest.mark.parametrize("cell", ["chat.repeat", "chat.single"])
def test_other_seed_other_stream_same_sizes(cell):
    a, b = _stream(SEEDS[0], cell), _stream(SEEDS[1], cell)
    assert a[1] != b[1]
    assert [len(x) for x in a[0]] == [len(x) for x in b[0]]
    assert [len(x) for x in a[1]] == [len(x) for x in b[1]]


def test_text_repeats_and_token_budget():
    tp = dict(tiny_cell("chat.repeat").traffic, batch=64, repeat_frac=0.4)
    t = TEXT.TextTraffic(tp, 11)
    t.prefill()
    seen, again = set(t.asked), []
    for batch in t.batches(40):
        again += [r in seen for r in batch]
        seen.update(batch)
    # the exact share of repeats, and novel questions that happen to
    # equal an earlier one
    assert 0.4 <= np.mean(again) < 0.45
    from reference.encoder import _WORD_RE
    assert max(len(_WORD_RE.findall(r)) + 2 for r in seen) <= 24
