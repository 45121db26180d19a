"""The served path with its embedder: `CachedLLMService.handle`.

The system under test is the program's own stack: the embedder
(`EmbedderTrainer.embed_texts` over the configuration's encoder, with
weights made from the configuration's fixed ``weight_seed``, so that
``--seed`` changes only the traffic), the tiered `CacheService` and the serving
pipeline in front of them, which answers misses with its echo answerer
(``engine=None``).  Each window request is one ``handle`` call on one
batch of questions.

Set-up fills the cache with ``prefill_rows`` novel questions, then runs
batches until one has missed for every number of misses a window batch
can have (1 to ``batch``): ``n`` novel questions beside ``batch - n``
questions of the previous batch, which the hot tier still holds.  So
every insert shape is compiled before the window.

``hit_recall`` is pooled over ``probe_rounds`` cache states after the
window: the state the window left, then one after each further IVF
rebuild, which set-up batches of novel questions bring about.  The
recall of one state swings with how evenly its last k-means filled the
inverted lists; the pool does not depend on where the window stopped.

The check, after the window and with the program's state freed:

* ``embed_gap``: the widest L2 distance between the program's
  embedding of a sampled window row and the reference encoder's
  (float32), over ``sample_rows`` rows drawn from the seed;
* ``false_hits``: sampled hits whose answer belongs to no stored
  question at reference cosine ``threshold - cos_band`` or more;
* ``bad_answers``: answers that are neither a stored question's answer
  (hits) nor the answer to a question of the same batch (misses);
* ``missing_answers``: questions left without an answer;
* ``readback_missing``: rows the last admitting commit stored that a
  plan of their own embeddings does not serve back with their answer.
"""
from __future__ import annotations

import re
import sys
import time
from types import SimpleNamespace

import numpy as np

from harness import cells, counters
from harness.recall import recall
from ops.cascade import served_shape
from harness.weights import encoder_shapes, make_encoder_params

SPANS = ("request", "embed", "plan", "generate", "commit", "maintenance")
_ANSWER = re.compile(r"^answer\((.*)\)$", re.S)


def sizes(cfg: dict) -> dict:
    """The published widths, with the program's own rotary base, norm
    epsilon and token budget (its departures, in ``program``)."""
    m = {k: cfg[k] for k in ("num_hidden_layers", "hidden_size",
                             "intermediate_size", "num_attention_heads",
                             "vocab_size")}
    m.update({k: cfg["program"][k] for k in ("rope_theta", "norm_eps",
                                              "max_tokens")})
    return m


def build_embedder(cfg: dict):
    """The program's embedder at the configuration's sizes, on weights
    made from the configuration's fixed ``weight_seed``;
    -> (embed_fn, sizes, trainer)."""
    import jax
    from repro.configs import get_config
    from repro.core.trainer import EmbedderTrainer, FinetuneConfig
    from repro.data.tokenizer import HashTokenizer
    from repro.models import init_lm
    from repro.models.param import split

    m = sizes(cfg)
    d, h = m["hidden_size"], m["num_attention_heads"]
    enc = get_config(cfg["program"]["embedder"]).replace(
        n_layers=m["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=h, head_dim=d // h, d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], norm_eps=m["norm_eps"],
        rope_theta=m["rope_theta"])
    want = jax.tree_util.tree_map(
        lambda s: tuple(s.shape), split(init_lm(enc, abstract=True))[0])
    got = encoder_shapes(m)
    if want != got:
        raise RuntimeError(f"the program's encoder tree {want} is not the "
                           f"benchmark's {got}")
    params = make_encoder_params(m, cfg["program"]["weight_seed"])
    tok = HashTokenizer(vocab_size=m["vocab_size"])
    trainer = EmbedderTrainer(enc, FinetuneConfig(max_len=m["max_tokens"]),
                              params=params)
    return trainer.make_embed_fn(tok), m, trainer


class System:
    span_names = SPANS

    def __init__(self, cell, seed: int, trace: bool):
        from repro.cache_service import CacheConfig, CacheService, \
            TieringConfig
        from repro.data.tokenizer import HashTokenizer
        from repro.obs import Telemetry, Tracer
        from repro.serving.engine import CachedLLMService

        t0 = time.perf_counter()
        self.cell, self.seed = cell, int(seed)
        cfg, tp = cell.config, cell.traffic
        self.threshold = float(cfg["cache"]["threshold"])
        self.limits = cfg["limits"]
        self.batch = int(tp["batch"])
        gen = cells.load_module("traffic", tp["generator"])
        self.traffic = gen.TextTraffic(tp, seed)
        self.record_every = int(tp["record_every"])
        embed, self.m, trainer = build_embedder(cfg)
        self.recorded = {}               # window batch -> embeddings
        self.n_batches = 0
        self._record = False

        def embed_fn(texts):
            e = self.live.embed(texts)
            if self._record:
                self.recorded[self.n_batches] = e
            return e

        telemetry = Telemetry(tracer=Tracer(annotate_xla=trace,
                                            keep=10 ** 7))
        tc = cfg["cache"]["tiering"]
        cache = CacheService(CacheConfig(
            dim=self.m["hidden_size"], threshold=self.threshold,
            telemetry=telemetry, tiering=TieringConfig(**tc)))
        svc = CachedLLMService(embed_fn, cache, None,
                               HashTokenizer(vocab_size=self.m["vocab_size"]),
                               telemetry=telemetry)
        self.live = SimpleNamespace(svc=svc, cache=cache, trainer=trainer,
                                    embed=embed, telemetry=telemetry)
        self.cascade = served_shape(tc, self.batch, self.m["hidden_size"])
        self.log = []                    # (texts, served) of every batch
        self.setup_s = {"build": time.perf_counter() - t0}
        prev = None
        for rows in self.traffic.prefill():
            prev = self._serve(rows)
        self.setup_s["fill"] = time.perf_counter() - t0 - sum(
            self.setup_s.values())
        todo = set(range(1, self.batch + 1))
        for _ in range(3 * self.batch):
            if not todo:
                break
            n = max(todo)
            novel = [self.traffic.novel() for _ in range(n)]
            self.traffic.asked.extend(novel)
            prev = self._serve(novel + prev[:self.batch - n])
            todo.discard(sum(not o.cache_hit for o in self.log[-1][1]))
        if todo:
            raise RuntimeError(f"set-up never missed {sorted(todo)} times "
                               f"in one batch")
        self.n_prefill = len(self.log)
        self.setup_s["shapes"] = time.perf_counter() - t0 - sum(
            self.setup_s.values())
        telemetry.tracer.drain()
        self.before = counters.counters(cache)

    def _serve(self, rows):
        out = self.live.svc.handle(rows)
        self.log.append((rows, out))
        return rows

    # -- window ---------------------------------------------------------
    def step(self):
        rows = self.traffic.batches(1)[0]
        self.n_batches += 1
        self._record = self.n_batches % self.record_every == 0
        t = time.perf_counter()
        out = self.live.svc.handle(rows)
        dt = time.perf_counter() - t
        self.log.append((rows, out))
        return dt, len(rows)

    # -- after the window -------------------------------------------------
    def after_window(self):
        from repro.cache_service.protocol import CacheRequest
        self._record = False
        live = self.live
        counters.report(self.before, counters.counters(live.cache),
                        self.setup_s)
        self.spans = {}
        for root in live.telemetry.tracer.drain():
            for s in root.walk():
                self.spans.setdefault(s.name, []).append(s.duration_s)
        self.n_window = len(self.log)
        window = self.log[self.n_prefill:]
        means = ", ".join(f"{k} {1e3 * np.mean(v):.3f}" for k, v in
                          self.spans.items() if k != "request")
        print(f"bench: mean span ms: {means}", file=sys.stderr)
        self.failed = sum(i >= len(out) or out[i] is None
                          or out[i].response is None
                          for rows, out in window for i in range(len(rows)))
        self.tokens = [self._n_tokens(t) for rows, _ in window for t in rows]

        # the set-up's batch, so that a one-row cell probes in few calls
        step = int(self.cell.traffic["prefill_batch"])

        def plan(texts):
            embs = live.embed(texts)
            hit, vid, resp = [], [], []
            for lo in range(0, len(texts), step):
                chunk = embs[lo:lo + step]
                pad = step - len(chunk)
                if pad:
                    chunk = np.concatenate([chunk, np.repeat(chunk[:1], pad,
                                                             0)])
                p = live.cache.plan(CacheRequest.build(chunk, 0),
                                    coalesce=False)
                n = step - pad
                hit += list(p.hit[:n])
                vid += list(p.top_value_ids[:n])
                resp += list(p.responses[:n])
            return embs, np.asarray(hit), np.asarray(vid), resp

        # read back the rows of the last commit that stored any
        last = next((rows, out) for rows, out in reversed(self.log)
                    if any(not o.cache_hit for o in out))
        stored = [(t, o.response) for t, o in zip(*last) if not o.cache_hit]
        _, hit, _, resp = plan([t for t, _ in stored])
        self.readback_missing = int(sum(
            not (h and r == want) for h, r, (_, want) in zip(hit, resp,
                                                             stored)))
        # recall against exact brute force over the live rows, pooled
        # over several cache states: the first as the window left it,
        # each later one after the next IVF rebuild
        good = n_hits = 0
        for r in range(int(self.cell.traffic["probe_rounds"])):
            if r and not self._next_rebuild():
                break
            probe = self.traffic.probe()
            embs, hit, vid, _ = plan(probe)
            g, n = recall(live.cache, embs, np.zeros(len(probe), np.int32),
                          hit, vid, self.threshold)
            good, n_hits = good + g, n_hits + n
        self.hit_recall = good / n_hits if n_hits else 0.0
        print(f"bench: hit_recall {good} of {n_hits} exact hits",
              file=sys.stderr)

    def _next_rebuild(self, most: int = 64) -> bool:
        """Serve set-up batches of novel questions until the cache has
        rebuilt its IVF once more; False if it never does (a commit that
        stores nothing, which the read-back check fails)."""
        before = counters.counters(self.live.cache)["rebuilds"]
        for _ in range(most):
            rows = [self.traffic.novel() for _ in range(
                int(self.cell.traffic["prefill_batch"]))]
            self.traffic.asked.extend(rows)
            self._serve(rows)
            if counters.counters(self.live.cache)["rebuilds"] > before:
                return True
        return False

    def _n_tokens(self, text):
        from reference.encoder import n_tokens
        return n_tokens(text, self.m["max_tokens"])

    def layer_context(self) -> dict:
        return {"spans": self.spans, "tokens": self.tokens,
                "encoder": self.m, "cascade": self.cascade}

    # -- the check --------------------------------------------------------
    def check(self, control: bool = False) -> dict:
        """Readings beside their limits; with ``control`` also the
        control's readings (the fp8 reference in the program's place)."""
        from reference import encoder as ref
        rng = np.random.default_rng([self.seed % (1 << 63), 5])
        window = self.log[self.n_prefill:self.n_window]
        stored = {}
        for rows, out in self.log:
            for t, o in zip(rows, out):
                if o is not None and not o.cache_hit:
                    stored.setdefault(o.response, []).append(t)
        # embeddings of a seeded sample of recorded window rows
        rec = [(b, r) for b, e in sorted(self.recorded.items())
               for r in range(len(e))]
        pick = rng.choice(len(rec), min(len(rec), int(
            self.cell.traffic["sample_rows"])), replace=False)
        s_texts = [window[rec[i][0] - 1][0][rec[i][1]] for i in pick]
        s_prog = np.stack([self.recorded[rec[i][0]][rec[i][1]]
                           for i in pick])
        # hits and coalesced misses of the window, sampled
        hits, coal, bad, missing = [], [], 0, 0
        for rows, out in window:
            missing += max(len(rows) - len(out), 0)
            for t, o in zip(rows, out):
                if o is None or o.response is None:
                    missing += 1
                elif o.cache_hit:
                    if o.response in stored:
                        hits.append((t, o.response))
                    else:
                        bad += 1
                else:
                    mt = _ANSWER.match(o.response)
                    if not mt or mt.group(1) not in rows:
                        bad += 1
                    elif mt.group(1) != t:
                        coal.append((t, mt.group(1)))
        n_hit = int(self.cell.traffic["sample_hits"])
        hits = [hits[i] for i in rng.choice(len(hits), min(n_hit, len(hits)),
                                            replace=False)]
        texts = sorted({t for t, _ in hits} | {c for _, r in hits
                                               for c in stored[r]}
                       | {t for pair in coal for t in pair})
        params = make_encoder_params(
            self.m, self.cell.config["program"]["weight_seed"])
        e_ref = ref.embed_texts(params, s_texts + texts, self.m)
        at = {t: e_ref[len(s_texts) + i] for i, t in enumerate(texts)}
        band = self.threshold - float(self.limits["cos_band"])
        false = sum(max(float(at[t] @ at[c]) for c in stored[r]) < band
                    for t, r in hits)
        false += sum(float(at[a] @ at[b]) < band for a, b in coal)
        out = {
            "embed_gap": float(np.linalg.norm(s_prog - e_ref[:len(s_texts)],
                                              axis=1).max()),
            "false_hits": int(false),
            "bad_answers": int(bad),
            "missing_answers": int(missing),
            "readback_missing": self.readback_missing,
        }
        readings = {k: (v, self.limits[k]) for k, v in out.items()}
        print(f"bench: checked {len(s_texts)} embeddings, {len(hits)} hits, "
              f"{len(coal)} coalesced misses", file=sys.stderr)
        if control:
            e8 = ref.embed_texts(params, s_texts, self.m, matmul="fp8")
            readings["control_embed_gap"] = (float(np.linalg.norm(
                e8 - e_ref[:len(s_texts)], axis=1).max()),
                self.limits["embed_gap"])
        return readings

