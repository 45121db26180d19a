"""Row bucketing of the encoder call: ``EmbedderTrainer.embed_texts``
pads each chunk of at most ``batch_size`` rows to the next power of two
of its rows, and every real row comes out as a full 64-row chunk would
give it."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.trainer import EmbedderTrainer, FinetuneConfig
from repro.data import HashTokenizer

# bf16 keeps 8 bits of mantissa: one rounding step of a unit-norm entry
BF16_ATOL = 2.0 ** -8


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("modernbert-149m").reduced(vocab_size=512)
    tok = HashTokenizer(vocab_size=512)
    return EmbedderTrainer(cfg, FinetuneConfig(max_len=12)), tok


def _texts(n, k=0):
    return [f"question {k} number {i} about item {7 * i + k}"
            for i in range(n)]


def _full_chunks(trainer, tok, texts, rows=64):
    """Each text encoded inside a 64-row chunk filled with other
    questions, as the encoder ran before bucketing."""
    filler = _texts(rows, k=99)
    out = []
    for i in range(0, len(texts), rows):
        chunk = list(texts[i:i + rows])
        ids, mask = tok.encode_batch(chunk + filler[len(chunk):],
                                     trainer.ft.max_len)
        out.append(np.asarray(trainer._encode(trainer.params, ids,
                                              mask))[:len(chunk)])
    return np.concatenate(out)


@pytest.mark.parametrize("n", [1, 3, 17, 32, 64, 65, 130])
def test_bucketed_rows_match_full_chunks(tiny, n):
    trainer, tok = tiny
    texts = _texts(n)
    got = trainer.embed_texts(texts, tok)
    assert got.shape == (n, trainer.cfg.d_model)
    np.testing.assert_allclose(got, _full_chunks(trainer, tok, texts),
                               rtol=0, atol=BF16_ATOL)


def test_encode_rows_are_powers_of_two(tiny, monkeypatch):
    trainer, tok = tiny
    encode, rows = trainer._encode, []

    def counted(params, ids, mask):
        rows.append(ids.shape[0])
        return encode(params, ids, mask)

    monkeypatch.setattr(trainer, "_encode", counted)
    expect = {1: [1], 3: [4], 17: [32], 32: [32], 33: [64], 64: [64],
              65: [64, 1], 130: [64, 64, 2]}
    for n, want in expect.items():
        rows.clear()
        trainer.embed_texts(_texts(n), tok)
        assert rows == want, n
        assert all(r & (r - 1) == 0 and r <= 64 for r in rows)
    rows.clear()
    trainer.embed_texts(_texts(40), tok, batch_size=48)
    assert rows == [48]                      # the bucket stops at the cap
