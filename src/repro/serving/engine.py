"""Serving engine: batched prefill + decode with carried state.

``ServeEngine`` is the host-side loop around the pure ``prefill`` /
``decode_step`` functions (jitted once per shape).  It serves *batched
requests* — the end-to-end example drivers put the semantic cache in
front of this engine, which is exactly the deployment the paper targets
(cache hit -> skip the engine entirely).
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache_service.protocol import CacheBackend, CacheRequest
from repro.configs.base import ATTN, MAMBA, ModelConfig
from repro.data.tokenizer import HashTokenizer
from repro.models import decode_step as model_decode_step
from repro.models import prefill as model_prefill
from repro.obs import Telemetry
from repro.obs.registry import tenant_label
from repro.obs.trace import child
from repro.serving.frontend import stub_frontend_embeds


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, max_new) int32, the real rows only
    n_prompt: int
    n_generated: int
    cache_hit: bool = False
    padded_to: int = 0          # rows the device ran (real + padding)


# decode steps queued on the device ahead of the token being copied:
# ~200 ms of jamba2-3b's 32-row steps on a TPU v5e, longer than the
# ~100-130 ms host stalls measured there
STEPS_AHEAD = 16


def _select(logits, temperature, key):
    """Greedy (temperature 0) or sampled next tokens, (B, 1) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    g = jax.random.gumbel(key, logits.shape)
    return jnp.argmax(logits / temperature + g, axis=-1).astype(
        jnp.int32)[:, None]


class ServeEngine:
    """Batched autoregressive serving for any decoder config.

    ``tokenizer`` is a hash vocabulary of the model's own size.  The
    jitted functions are named, so a device trace shows ``jit_prefill``
    and ``jit_decode_step``; each picks its tokens on the device, and
    the decode step updates its state in place (the state is donated).
    """

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512):
        if cfg.is_encoder:
            raise ValueError(f"{cfg.name} is encoder-only; no decode path")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.tokenizer = HashTokenizer(vocab_size=cfg.vocab_size)
        # every mixer takes a pad mask, so ragged prompts can share a batch
        self.masked = all(s.mixer in (ATTN, MAMBA) for s in cfg.period)

        def prefill(pv, toks, mask, fe, key, temperature):
            logits, state = model_prefill(pv, cfg, toks, max_len, fe, mask)
            return _select(logits, temperature, key), logits, state

        def decode_step(pv, state, tok, key, temperature):
            logits, state = model_decode_step(pv, cfg, state, tok)
            key, sub = jax.random.split(key)
            return _select(logits, temperature, sub), logits, state, key

        self._prefill = jax.jit(prefill, static_argnums=5)
        self._decode = jax.jit(decode_step, static_argnums=4,
                               donate_argnums=1)

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 use_frontend: bool = False, mask: Optional[np.ndarray] = None
                 ) -> GenerationResult:
        """prompts: (B, S) int32, each row's real tokens marked by ``mask``
        ((B, S) bool, None = all real).  Rows are left-padded, so every
        row's last prompt token is the last column, where the prefill
        takes its logits.  The rows are padded with empty prompts to the
        next power of two: one compiled shape per bucket (1, 2, 4, ...),
        whatever the number of rows.  A config whose mixers take no pad mask
        runs ragged prompts one length at a time.  Greedy
        (temperature=0) or sampled, for exactly ``max_new_tokens``
        tokens: the first from the prefill, then one per decode step,
        each copied to the host while later steps run."""
        n, S = prompts.shape
        if max_new_tokens < 1 or S + max_new_tokens - 1 > self.max_len:
            raise ValueError(f"{S} prompt tokens and {max_new_tokens} new "
                             f"ones do not fit max_len {self.max_len}")
        mask = np.ones((n, S), bool) if mask is None else np.asarray(mask)
        if not self.masked and not mask.all():
            return self._by_length(prompts, mask, max_new_tokens,
                                   temperature, seed, use_frontend)
        pad_to = 1 << (n - 1).bit_length()
        if pad_to > n:
            e_ids, e_mask = self.tokenizer.encode("", S)
            prompts = np.concatenate([prompts, np.tile(e_ids, (pad_to - n, 1))])
            mask = np.concatenate([mask, np.tile(e_mask, (pad_to - n, 1))])
        # left-pad: each row's pads first, then its tokens in order
        order = np.argsort(mask, axis=1, kind="stable")
        ids = np.take_along_axis(prompts, order, axis=1)
        mask = np.take_along_axis(mask, order, axis=1)
        dev_mask = jnp.asarray(mask) if self.masked else None
        fe = (stub_frontend_embeds(self.cfg, pad_to, seed) if use_frontend
              else None)
        key = jax.random.PRNGKey(seed)
        out = np.zeros((pad_to, max_new_tokens), np.int32)
        with child("generate.prefill", rows=n, padded_to=pad_to):
            tok, logits, state = self._prefill(
                self.params, jnp.asarray(ids), dev_mask, fe, key, temperature)
            self._observe(0, logits, n)
            with child("generate.sync"):
                out[:, 0] = np.asarray(tok)[:, 0]
        with child("generate.decode", rows=n, padded_to=pad_to,
                   steps=max_new_tokens - 1):
            # each token is copied once STEPS_AHEAD more steps are queued
            # behind it, so the device runs on through a host stall
            pending = deque()
            for t in range(1, max_new_tokens):
                tok, logits, state, key = self._decode(
                    self.params, state, tok, key, temperature)
                self._observe(t, logits, n)
                pending.append((t, tok))
                if len(pending) > STEPS_AHEAD:
                    self._copy(out, *pending.popleft())
            for t, ready in pending:
                self._copy(out, t, ready)
        return GenerationResult(out[:n], n_prompt=S,
                                n_generated=max_new_tokens, padded_to=pad_to)

    @staticmethod
    def _copy(out: np.ndarray, t: int, tok) -> None:
        with child("generate.sync"):
            out[:, t] = np.asarray(tok)[:, 0]

    def _by_length(self, prompts, mask, max_new_tokens, temperature, seed,
                   use_frontend) -> GenerationResult:
        """Ragged prompts for mixers without a pad mask: one unpadded
        call per prompt length."""
        n, S = prompts.shape
        lengths = mask.sum(axis=1)
        out = np.zeros((n, max_new_tokens), np.int32)
        padded = 0
        for length in np.unique(lengths):
            rows = np.flatnonzero(lengths == length)
            ids = np.stack([prompts[r][mask[r]] for r in rows])
            res = self.generate(ids, max_new_tokens, temperature, seed,
                                use_frontend)
            out[rows] = res.tokens
            padded += res.padded_to
        return GenerationResult(out, n_prompt=S, n_generated=max_new_tokens,
                                padded_to=padded)

    def _observe(self, step: int, logits, rows: int) -> None:
        """Sees the prefill's logits (step 0) and each decode step's while
        they are on the device; ``rows`` real rows come first.  A no-op
        here, for a subclass to record them."""


@dataclass
class ServedRequest:
    query: str
    response: str
    cache_hit: bool
    score: float = 0.0


class CachedLLMService:
    """The paper's deployment: a semantic cache in front of an LLM.

    ``handle`` is a thin typed pipeline over any ``CacheBackend``
    (DESIGN.md §7): embed -> ``plan`` (per-row hit/miss verdicts,
    resolved responses, admission pre-decision, miss coalescing) ->
    generate one answer per miss *group* leader -> ``commit`` -> drive
    backend ``maintenance()`` between batches (this is what lets the
    warm-IVF rebuild run double-buffered off the hot path).  Backend
    features are discovered through ``capabilities()``, never hasattr.
    """

    def __init__(self, embed_fn, cache: CacheBackend,
                 engine: Optional[ServeEngine],
                 tokenizer: Optional[HashTokenizer] = None,
                 max_query_len: int = 32, max_new_tokens: int = 16,
                 coalesce: bool = True,
                 telemetry: Optional[Telemetry] = None):
        """``tokenizer`` is the LLM's (None = the engine's own, a hash
        vocabulary of the LLM's size; one of another size is refused).
        A batch's miss leaders are generated in one call, padded to the
        next power of two of their number.

        ``coalesce=False`` generates per miss row even for
        near-identical queries (the legacy behaviour).

        ``telemetry`` (None = adopt the backend's, so the whole stack
        shares one registry/tracer) wires the §10 spans and serving
        counters; each ``handle`` call produces one span tree rooted at
        ``request`` with embed/plan/generate/commit(/maintenance)
        children, and the engine observes the embed and generate stages
        into the shared ``stage_latency_seconds`` histogram (plan/
        commit/maintenance are observed by the backend itself)."""
        self.embed_fn = embed_fn          # list[str] -> (B, D) unit vectors
        if not isinstance(cache, CacheBackend):
            raise TypeError(
                f"cache backend {type(cache).__name__} does not implement "
                "the CacheBackend protocol (capabilities/plan/commit/"
                "maintenance/stats_snapshot); see "
                "repro.cache_service.protocol")
        self.cache = cache
        self.caps = cache.capabilities()
        self.engine = engine
        self.tok = tokenizer
        if engine is not None:
            self.tok = tokenizer or engine.tokenizer
            if self.tok.vocab_size != engine.cfg.vocab_size:
                raise ValueError(
                    f"tokenizer vocabulary {self.tok.vocab_size} is not "
                    f"the LLM's {engine.cfg.vocab_size}")
        self.max_query_len = max_query_len
        self.max_new_tokens = max_new_tokens
        self.coalesce = coalesce
        self.telemetry = (telemetry
                          or getattr(cache, "telemetry", None)
                          or Telemetry())
        reg = self.telemetry.registry
        self._stage_h = self.telemetry.stage_histogram()
        self._m_requests = reg.counter(
            "serve_requests_total", "queries handled", labels=("tenant",))
        self._m_hits = reg.counter(
            "serve_hits_total", "queries served from cache",
            labels=("tenant",))
        self._m_misses = reg.counter(
            "serve_misses_total", "queries that missed", labels=("tenant",))
        self._c_generations = reg.counter(
            "serve_generations_total", "LLM generations (group leaders)"
            ).labels()
        self._c_coalesced = reg.counter(
            "serve_coalesced_misses_total",
            "misses served by another row's generation").labels()
        self._c_gen_tokens = reg.counter(
            "serve_generated_tokens_total",
            "tokens the LLM generated for real rows").labels()
        self._c_gen_padded = reg.counter(
            "serve_generate_padded_rows_total",
            "empty rows that padded LLM calls to their bucket").labels()
        self._c_maintenance = reg.counter(
            "serve_maintenance_calls_total",
            "between-batch maintenance() calls").labels()
        self._trace = itertools.count()

    def _llm_answer(self, queries: List[str]) -> List[str]:
        if self.engine is None:  # degenerate echo backend for tests
            return [f"answer({q})" for q in queries]
        ids, mask = self.tok.encode_batch(queries, self.max_query_len)
        res = self.engine.generate(ids, self.max_new_tokens, mask=mask)
        self._c_gen_tokens.inc(res.tokens.size)
        self._c_gen_padded.inc(res.padded_to - len(queries))
        return [" ".join(map(str, row)) for row in res.tokens]

    def handle(self, queries: List[str],
               tenant: int = 0) -> List[ServedRequest]:
        if not self.caps.tenants and np.any(np.asarray(tenant) != 0):
            raise ValueError(
                f"cache backend {type(self.cache).__name__} is not "
                "tenant-aware; serving tenant "
                f"{tenant} through it would break isolation")
        tracer = self.telemetry.tracer
        lab = tenant_label(np.asarray(tenant))
        trace_id = next(self._trace)
        with tracer.span("request", tenant=lab, trace_id=trace_id,
                         n=len(queries)):
            t0 = time.perf_counter()
            with tracer.span("embed", tenant=lab):
                embs = self.embed_fn(queries)
            self._stage_h.observe(time.perf_counter() - t0,
                                  stage="embed", tenant=lab)
            with tracer.span("plan", tenant=lab):
                # texts ride along so a §11 backend can retain them for
                # re-embedding admitted rows under a refreshed embedder
                plan = self.cache.plan(
                    CacheRequest.build(embs, tenant, trace_id=trace_id,
                                       texts=queries),
                    coalesce=self.coalesce)

            # one generation per miss-group leader serves the whole
            # group (with coalesce=False the plan's map degenerates to
            # one group per miss row, so this needs no special-casing)
            leaders = plan.leader_rows()
            t0 = time.perf_counter()
            with tracer.span("generate", tenant=lab,
                             n_leaders=len(leaders)):
                answers = dict(zip(
                    leaders,
                    self._llm_answer([queries[i] for i in leaders])
                    if leaders else []))
            self._stage_h.observe(time.perf_counter() - t0,
                                  stage="generate", tenant=lab)
            responses: List[Optional[str]] = [None] * len(queries)
            for i in plan.miss_rows():
                responses[int(i)] = answers[int(plan.miss_leader[i])]

            with tracer.span("commit", tenant=lab):
                receipt = self.cache.commit(plan, responses)
            self._m_requests.inc(len(queries), tenant=lab)
            self._m_hits.inc(int(plan.hit.sum()), tenant=lab)
            self._m_misses.inc(int((~plan.hit).sum()), tenant=lab)
            self._c_generations.inc(len(leaders))
            self._c_coalesced.inc(plan.n_coalesced)
            if receipt.rebuild_due:
                # between-batch maintenance: publish/start the
                # background IVF rebuild without stalling any request
                with tracer.span("maintenance", tenant=lab):
                    self.cache.maintenance()
                self._c_maintenance.inc()

        out: List[Optional[ServedRequest]] = [None] * len(queries)
        for i, q in enumerate(queries):
            if plan.hit[i]:
                out[i] = ServedRequest(q, plan.responses[i], True,
                                       float(plan.scores[i]))
            else:
                out[i] = ServedRequest(q, responses[i], False)
        return out  # type: ignore

    def stats(self) -> Dict[str, object]:
        """Unified telemetry snapshot: the serving counters plus the
        backend's ``stats_snapshot()`` nested under ``"backend"`` (the
        protocol allows a mapping or a typed object with ``to_dict()``
        — both normalise to a plain dict here).  Serving keys live at
        the top level, so a backend's plan-level "hits" can never
        shadow the pipeline's."""
        reg = self.telemetry.registry
        snap = self.cache.stats_snapshot()
        backend = snap.to_dict() if hasattr(snap, "to_dict") else dict(snap)
        return {"backend": backend,
                "requests": int(reg.value("serve_requests_total")),
                "hits": int(reg.value("serve_hits_total")),
                "misses": int(reg.value("serve_misses_total")),
                "generations": int(reg.value("serve_generations_total")),
                "coalesced_misses": int(
                    reg.value("serve_coalesced_misses_total")),
                "maintenance_calls": int(
                    reg.value("serve_maintenance_calls_total")),
                "hit_rate": self.hit_rate}

    @property
    def hit_rate(self) -> float:
        reg = self.telemetry.registry
        hits = reg.value("serve_hits_total")
        n = hits + reg.value("serve_misses_total")
        return hits / n if n else 0.0
