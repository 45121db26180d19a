"""End-to-end behaviour tests for the paper's system.

The full loop: domain corpus -> fine-tune compact embedder (1 epoch,
online contrastive, clip 0.5) -> semantic cache in front of an LLM
serving engine -> repeated paraphrased queries hit the cache; and the
paper's headline comparisons at smoke scale.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    EmbedderTrainer, FinetuneConfig, SemanticCache, TemplateGenerator,
    generate_synthetic_pairs, records_to_dataset,
)
from repro.data import HashTokenizer, make_pair_dataset, make_query_stream, sample_query
from repro.models import init_lm, split
from repro.serving import CachedLLMService, ServeEngine


@pytest.fixture(scope="module")
def finetuned_embedder():
    cfg = get_config("modernbert-149m").reduced(vocab_size=4096)
    tok = HashTokenizer(vocab_size=cfg.vocab_size)
    train = make_pair_dataset("medical", 256, seed=0)
    ft = FinetuneConfig(epochs=2, batch_size=16, max_len=24, lr=3e-4)
    trainer = EmbedderTrainer(cfg, ft)
    trainer.fit(train, tok)
    return cfg, tok, trainer


def test_cache_hit_rate_improves_with_finetuning(finetuned_embedder):
    """The system-level payoff claimed by the paper: a fine-tuned
    compact embedder gives a better true-hit/false-hit trade-off than
    the untuned base in an actual serving loop."""
    cfg, tok, trainer = finetuned_embedder
    base = EmbedderTrainer(cfg, FinetuneConfig(max_len=24))  # untuned

    stream = make_query_stream("medical", 150, seed=3, repeat_frac=0.4)

    def run(embed_trainer):
        cache = SemanticCache(capacity=1024, dim=cfg.d_model, threshold=0.92)
        svc = CachedLLMService(embed_trainer.make_embed_fn(tok), cache,
                               engine=None, tokenizer=tok)
        # correctness oracle: a hit is TRUE if the hit query shares
        # (entity, aspect) with the query that inserted the response
        inserted = {}
        true_hits = false_hits = 0
        for q in stream:
            r = svc.handle([q.text])[0]
            key = (q.entity, q.aspect)
            if r.cache_hit:
                src = inserted.get(r.response)
                if src == key:
                    true_hits += 1
                else:
                    false_hits += 1
            else:
                inserted[r.response] = key
        return true_hits, false_hits

    th_ft, fh_ft = run(trainer)
    th_b, fh_b = run(base)
    # fine-tuned must find strictly more true hits without exploding
    # false hits
    assert th_ft > th_b, (th_ft, fh_ft, th_b, fh_b)
    assert fh_ft <= max(fh_b, 2), (th_ft, fh_ft, th_b, fh_b)


def test_synthetic_data_finetune_beats_base(finetuned_embedder):
    """Table-1 mechanism at smoke scale: fine-tuning on purely synthetic
    pairs (dual-labeled pipeline output) improves real-pair metrics."""
    cfg, tok, _ = finetuned_embedder
    rng = np.random.default_rng(0)
    unlabeled = [sample_query(rng, "medical") for _ in range(100)]
    records = generate_synthetic_pairs(unlabeled, TemplateGenerator(1),
                                       n_pos=1, n_neg=1)
    synth_ds = records_to_dataset(records)
    real_eval = make_pair_dataset("medical", 128, seed=77)

    base = EmbedderTrainer(cfg, FinetuneConfig(max_len=24))
    before = base.evaluate(real_eval, tok)
    ft = EmbedderTrainer(cfg, FinetuneConfig(epochs=2, batch_size=16,
                                             max_len=24, lr=3e-4))
    ft.fit(synth_ds, tok)
    after = ft.evaluate(real_eval, tok)
    assert after["ap"] > before["ap"], (before["ap"], after["ap"])


def test_full_serving_stack_with_real_llm():
    """Cache in front of an actual JAX decoder: miss -> generate via the
    engine; repeat -> hit without generation."""
    dec_cfg = get_config("granite-moe-3b-a800m").reduced()
    pv, _ = split(init_lm(dec_cfg, jax.random.PRNGKey(0)))
    engine = ServeEngine(dec_cfg, pv, max_len=48)

    enc_cfg = get_config("modernbert-149m").reduced(vocab_size=4096)
    tok = HashTokenizer(vocab_size=enc_cfg.vocab_size)
    trainer = EmbedderTrainer(enc_cfg, FinetuneConfig(max_len=24))
    cache = SemanticCache(capacity=128, dim=enc_cfg.d_model, threshold=0.99)
    svc = CachedLLMService(trainer.make_embed_fn(tok), cache, engine,
                           max_new_tokens=4)
    q = ["What are the symptoms of early-stage diabetes?"]
    r1 = svc.handle(q)[0]
    assert not r1.cache_hit and len(r1.response) > 0
    r2 = svc.handle(q)[0]
    assert r2.cache_hit and r2.response == r1.response
    st = svc.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["generations"] == 1 and st["requests"] == 2
