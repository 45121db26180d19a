"""Jamba2-3B behind the cache: the published config, the Mamba mixer's
Jamba parts, pad handling, and prefill + decode through `ServeEngine`
against the plain reference (`bench/reference/jamba.py`), on the CPU
at small widths with the 14-layer period kept.

Tolerances.  The program runs here in float32 (``dtype="float32"``) on
the same weights as the reference, which computes at
``Precision.HIGHEST``; the two differ only in the order of their sums
(chunked associative scan against a sequential one, fused against
separate products), about 1e-6 relative per layer, so logits agree to
1e-4 relative after 28 layers and 8 decode steps.  Batching changes
only the shapes of the same products, so a row batched or alone agrees
to 1e-5.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SSMConfig, get_config
from repro.configs.base import ATTN, MAMBA
from repro.core import SemanticCache
from repro.core.embedders import HashNgramEmbedder
from repro.data import HashTokenizer
from repro.launch.serve import decoder_config
from repro.models import attention, blocks, init_lm, mamba, split
from repro.models.param import Initializer
from repro.obs import Telemetry, Tracer
from repro.serving import CachedLLMService, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from harness.jamba_weights import jamba_sizes, make_jamba_params  # noqa: E402
from reference import jamba as ref  # noqa: E402

# the published keys of the benchmark's config file, at small widths
SMALL = {"num_hidden_layers": 28, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 1,
         "intermediate_size": 96, "vocab_size": 512, "mamba_expand": 2,
         "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 8,
         "attn_layer_period": 14, "attn_layer_offset": 7,
         "rms_norm_eps": 1e-6}
REL = 1e-4          # program against reference, float32 (module docstring)
BATCHED = 1e-5      # one row batched against alone, float32
PROMPTS = ["What are the symptoms of asthma?",
           "How do doctors manage a long and complicated case of chronic "
           "kidney disease in an adult?",
           "Why?"]


def _small():
    """(program config, sizes, f32 weights) at SMALL's widths."""
    m = jamba_sizes(SMALL)
    cfg = get_config("jamba2-3b").replace(
        n_layers=28, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
        d_ff=96, vocab_size=512, dtype="float32",
        ssm=SSMConfig(d_state=8, d_conv=4, expand=2, dt_rank=8,
                      inner_norms=True))
    pv = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make_jamba_params(m, 11))
    return cfg, m, pv


@pytest.fixture(scope="module")
def small():
    return _small()


def _rel(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


class Recording(ServeEngine):
    """Keeps the real rows' logits of every step on the host."""

    def _observe(self, step, logits, rows):
        self.seen.append(np.asarray(logits[:rows], np.float32))


def _generate(engine, texts, n_new=8):
    """-> (tokens (B, n_new), logits (B, n_new, V)) of real rows."""
    engine.seen = []
    ids, mask = engine.tokenizer.encode_batch(texts, 24)
    res = engine.generate(ids, n_new, mask=mask)
    return res, np.stack(engine.seen, axis=1), (ids, mask)


def test_published_config():
    cfg = get_config("jamba2-3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        28, 2560, 20, 1, 128, 8192, 65536)
    assert not cfg.use_rope and cfg.tie_embeddings
    assert cfg.norm_eps == 1e-6 and cfg.param_dtype == "bfloat16"
    s = cfg.ssm
    assert (s.d_state, s.d_conv, s.expand, s.dt_rank, s.inner_norms) == (
        16, 4, 2, 160, True)
    # HF Jamba: layer i is attention iff i % 14 == 7
    assert [i for i, sp in enumerate(cfg.layer_specs())
            if sp.mixer == ATTN] == [7, 21]
    assert all(sp.mixer in (ATTN, MAMBA) for sp in cfg.period)
    # embedding 167,772,160 + 26 Mamba layers of 41,241,792 (conv and dt
    # biases and the dt/B/C norms included) + 2 attention layers of
    # 13,762,560 + 28 MLPs of 62,914,560 + norms
    assert cfg.param_count() == 3_029_337_472


def test_jamba_1_5_large_has_the_same_mixer():
    assert get_config("jamba-1.5-large-398b").ssm.inner_norms


def test_a_log_init_gives_the_s4d_spectrum():
    """A = -exp(A_log) with A_log initialised to log([1..N]): -[1..N]."""
    cfg = get_config("jamba2-3b").reduced()
    p, _ = split(mamba.init_mamba(Initializer(jax.random.PRNGKey(0)), cfg))
    N = cfg.ssm.d_state
    A = -np.exp(np.asarray(p["A_log"]))
    np.testing.assert_allclose(A, -np.broadcast_to(np.arange(1, N + 1),
                                                   A.shape), rtol=1e-6)
    for k, n in (("dt_norm", cfg.ssm.dt_rank), ("B_norm", N), ("C_norm", N)):
        np.testing.assert_array_equal(np.asarray(p[k]), np.ones(n))


@pytest.mark.parametrize("pos", [0, 7], ids=["mamba", "attention"])
def test_one_layer_matches_the_reference(small, pos):
    """One layer with random inner-norm scales, A_log and biases: the
    program's block against the reference's HF equations."""
    cfg, m, pv = small
    lp = jax.tree_util.tree_map(lambda a: a[1], pv["layers"][f"pos{pos}"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, m["d"]))
    y, _ = blocks.apply_full(lp, cfg, cfg.period[pos], x,
                             jnp.arange(20, dtype=jnp.int32))
    layer = ref.attention_layer if pos == 7 else ref.mamba_layer
    with jax.default_matmul_precision("highest"):
        want = layer(x, lp, eps=m["eps"], matmul="f32")
    assert _rel(np.asarray(y), np.asarray(want)) < REL


def test_mamba_inner_norms_are_applied(small):
    """Scaling the dt norm's gain moves the mixer's output in the
    program as in the reference."""
    cfg, m, pv = small
    lp = jax.tree_util.tree_map(lambda a: a[0], pv["layers"]["pos0"])
    lp2 = dict(lp, mixer=dict(lp["mixer"],
                              dt_norm=2.0 * lp["mixer"]["dt_norm"]))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, m["d"]))
    ys = [np.asarray(mamba.apply_full(p["mixer"], cfg, x))
          for p in (lp, lp2)]
    assert _rel(ys[1], ys[0]) > 1e-2


def test_engine_matches_reference(small):
    """Prefill then decode through the engine, three ragged prompts in a
    bucket of four: logits at the prompt's last token and every decode
    step against the reference's full forward over prompt + answer."""
    cfg, m, pv = small
    engine = Recording(cfg, pv, max_len=32)
    res, logits, (ids, mask) = _generate(engine, PROMPTS)
    assert res.padded_to == 4 and res.tokens.shape == (3, 8)
    prompts = [i[k].tolist() for i, k in zip(ids, mask)]
    want = ref.teacher_forced(pv, prompts, res.tokens.tolist(), m)
    assert _rel(logits, want) < REL
    np.testing.assert_array_equal(res.tokens, want.argmax(-1))


def test_pad_invariance(small):
    """A row alone, unpadded, gets the logits it gets batched with longer
    and shorter prompts and with bucket padding rows."""
    cfg, m, pv = small
    engine = Recording(cfg, pv, max_len=32)
    alone, l_alone, _ = _generate(engine, PROMPTS[:1])
    batched, l_batched, _ = _generate(engine, PROMPTS + PROMPTS[:2])
    assert batched.padded_to == 8
    assert _rel(l_batched[0], l_alone[0]) < BATCHED
    np.testing.assert_array_equal(batched.tokens[0], alone.tokens[0])
    np.testing.assert_array_equal(batched.tokens[3], alone.tokens[0])


def test_prefill_marks_pads_in_the_kv_cache(small):
    cfg, m, pv = small
    lp = jax.tree_util.tree_map(lambda a: a[0], pv["layers"]["pos7"])
    mask = np.array([[False, False, True, True], [True, True, True, True]])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 4, m["d"]))
    _, cache = attention.apply_prefill(
        lp["mixer"], cfg, x, jnp.arange(4, dtype=jnp.int32),
        attention.init_cache(cfg, 2, 6), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  [[-1, -1, 2, 3, -1, -1],
                                   [0, 1, 2, 3, -1, -1]])


@pytest.mark.parametrize("n,want", [
    (1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (17, 32)])
def test_rows_pad_to_the_next_power_of_two(small, n, want):
    cfg, m, pv = small
    engine = ServeEngine(cfg, pv, max_len=32)
    ids = np.full((n, 6), 5, np.int32)
    res = engine.generate(ids, 2)
    assert res.padded_to == want and res.tokens.shape == (n, 2)


def test_service_generates_through_the_engine(small):
    """Misses go to the engine with the LLM's own tokenizer; the service
    counts generated tokens and padding rows, and the engine's spans
    hang under ``generate``."""
    cfg, m, pv = small
    engine = ServeEngine(cfg, pv, max_len=32)
    emb = HashNgramEmbedder(dim=64)
    tel = Telemetry(tracer=Tracer())
    svc = CachedLLMService(emb.embed, SemanticCache(
        capacity=64, dim=64, threshold=0.99, telemetry=tel), engine,
        max_query_len=24, max_new_tokens=5, telemetry=tel)
    out = svc.handle(PROMPTS)
    ids, mask = engine.tokenizer.encode_batch(PROMPTS, 24)
    want = engine.generate(ids, 5, mask=mask)
    assert [o.response for o in out] == [" ".join(map(str, r))
                                         for r in want.tokens]
    reg = tel.registry
    assert reg.value("serve_generated_tokens_total") == 3 * 5
    assert reg.value("serve_generate_padded_rows_total") == 1
    gen = tel.tracer.last_root().find("generate")
    assert gen.stage_names() == ["generate.prefill", "generate.decode"]
    dec = gen.find("generate.decode")
    assert dec.attrs == {"rows": 3, "padded_to": 4, "steps": 4}
    assert sum(s.name == "generate.sync" for s in gen.walk()) == 5


def test_service_refuses_a_tokenizer_of_another_vocabulary(small):
    cfg, m, pv = small
    engine = ServeEngine(cfg, pv, max_len=32)
    with pytest.raises(ValueError, match="vocabulary"):
        CachedLLMService(lambda t: None, SemanticCache(capacity=8, dim=8),
                         engine, HashTokenizer(vocab_size=50368))


def test_decoder_without_a_pad_mask_serves_ragged_misses():
    """xLSTM's mixers take no pad mask: the service still answers a
    batch of ragged questions, each row as it is answered alone."""
    cfg = get_config("xlstm-125m").reduced()
    pv, _ = split(init_lm(cfg, jax.random.PRNGKey(0)))
    engine = ServeEngine(cfg, pv, max_len=32)
    assert not engine.masked
    svc = CachedLLMService(HashNgramEmbedder(dim=64).embed, SemanticCache(
        capacity=64, dim=64, threshold=0.99), engine, max_query_len=24,
        max_new_tokens=3)
    out = svc.handle(PROMPTS)
    for q, o in zip(PROMPTS, out):
        ids, mask = engine.tokenizer.encode(q, 24)
        alone = engine.generate(ids[mask][None], 3).tokens[0]
        assert o.response == " ".join(map(str, alone))


def test_engine_jits_are_named(small):
    cfg, m, pv = small
    engine = ServeEngine(cfg, pv, max_len=32)
    assert engine._prefill.__name__ == "prefill"
    assert engine._decode.__name__ == "decode_step"


def test_decode_step_updates_its_state_in_place(small):
    """The decode step donates its state: the state it was given is
    consumed, so a 32-row step allocates no second copy of it."""
    cfg, m, pv = small
    engine = ServeEngine(cfg, pv, max_len=32)
    ids = np.full((2, 6), 5, np.int32)
    key = jax.random.PRNGKey(0)
    tok, _, state = engine._prefill(pv, jnp.asarray(ids), None, None, key,
                                    0.0)
    engine._decode(pv, state, tok, key, 0.0)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(state))


def test_launch_serves_jamba2_at_published_widths():
    assert decoder_config("jamba2-3b", smoke=False) == get_config(
        "jamba2-3b")
    assert decoder_config("jamba2-3b", smoke=True).d_model <= 128
    # 3.8B float32 weights would not leave room for the cache
    assert decoder_config("phi3-mini-3.8b", smoke=False).d_model <= 128
