"""The served path with an LLM behind the cache: `CachedLLMService.handle`
over the embedder, the tiered cache and `ServeEngine` running Jamba.

The system is `served_text`'s (its embedder, cache, traffic, fill,
insert shapes, recall probe and embedder check, loaded through
``cells.load_module``) with the configuration's LLM added: the
program's ``program.llm`` config at the file's published sizes, on
bfloat16 weights made from ``program.weight_seed``
(`harness.jamba_weights`), behind a second `CachedLLMService` that
shares the cache.  The 17,408-row fill, the insert shapes and the
rebuilds of the recall probe go through `served_text`'s echo service;
each window request is one ``handle`` call of the LLM's service, whose
misses are answered by one ``max_new_tokens``-token greedy generation
per miss group.  Set-up runs every leader bucket (1, 2, 4, ... up to
the power of two at or above ``batch``) through the engine, so the window compiles nothing.

The first window call and every ``record_every``-th after it keep
their embeddings (for `served_text`'s check) and, on the device, the
logits of one of their generations (a row drawn from the seed) at the
prompt's last token and at every decode step; after the window those
come to the host.  The check adds, with the program's state freed:

* ``logit_gap``: the largest ||l_prog - l_ref|| / ||l_ref|| over the
  positions of ``sample_generations`` kept generations drawn from the
  seed, against the plain reference (`reference.jamba`, float32) fed
  the prompt and the program's own answer;
* ``bad_tokens``: positions of those generations whose answer token is
  not the argmax of the program's logits there, or not the reference's
  where no error of the program's size could change the argmax; the
  reference, fed the program's answer, cannot see a wrong token alone;
* ``bad_answers``: window misses whose answer is not the generation,
  in the same batch, of their own question or of another miss of the
  batch (their group leader); a follower and its leader are held to
  the cache's threshold less ``cos_band`` by the reference embedder,
  in ``false_hits``.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import replace

import numpy as np

from harness import cells, counters
from harness.jamba_weights import jamba_shapes, jamba_sizes, \
    make_jamba_params

served_text = cells.load_module("systems", "served_text")
SPANS = served_text.SPANS + ("generate.prefill", "generate.decode",
                             "generate.sync")


def llm_config(cfg: dict):
    """The program's LLM config at the file's published sizes."""
    from repro.configs import get_config
    m = jamba_sizes(cfg)
    base = get_config(cfg["program"]["llm"])
    ssm = replace(base.ssm, d_state=m["d_state"], d_conv=m["d_conv"],
                  expand=cfg["mamba_expand"], dt_rank=m["dt_rank"])
    period = tuple(base.period[m["offset"]] if i == m["offset"]
                   else base.period[0] for i in range(m["stack"]))
    return base.replace(
        n_layers=m["layers"], d_model=m["d"], n_heads=m["heads"],
        n_kv_heads=m["kv_heads"], head_dim=m["head_dim"], d_ff=m["ff"],
        vocab_size=m["vocab"], norm_eps=m["eps"], ssm=ssm, period=period)


def embedder_view(cell):
    """The cell as `served_text` reads it: the embedder's sizes and
    program at the top, beside this file's cache and limits."""
    cfg = cell.config
    view = dict(cfg["embedder"], cache=cfg["cache"], limits=cfg["limits"])
    return replace(cell, config=view)


def _pick(logits, row):
    import jax
    return jax.lax.dynamic_index_in_dim(logits, row, keepdims=False)


def build_engine(cfg: dict, n_new: int, seed: int):
    """The program's engine over weights from the seed, holding a prompt
    and ``n_new`` tokens a row; -> (engine, sizes).  The weights' tree
    must be the program's, leaf for leaf."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_lm
    from repro.models.param import split
    from repro.serving.engine import ServeEngine

    class Engine(ServeEngine):
        """Keeps each call's prompts, mask and answers on the host, and
        while ``keep`` is set one generation's logits a call (a row
        drawn from the seed) on the device."""
        keep = False

        def generate(self, prompts, *a, **kw):
            res = super().generate(prompts, *a, **kw)
            self.calls.append((prompts, kw["mask"], res.tokens))
            return res

        def _observe(self, step, logits, rows):
            if not self.keep:
                return
            if step == 0:
                row = int(self.rng.integers(rows))
                self.kept.append({"call": len(self.calls), "row": row,
                                  "at": jnp.asarray(row, np.int32),
                                  "logits": []})
            k = self.kept[-1]
            k["logits"].append(self.pick(logits, k["at"]))

    llm, m = llm_config(cfg), jamba_sizes(cfg)
    want = jax.tree_util.tree_map(
        lambda s: (tuple(s.shape), str(s.dtype)),
        split(init_lm(llm, abstract=True))[0])
    got = jax.tree_util.tree_map(lambda s: (s, "bfloat16"), jamba_shapes(m),
                                 is_leaf=lambda x: isinstance(x, tuple))
    if want != got:
        raise RuntimeError(f"the program's LLM tree {want} is not the "
                           f"benchmark's {got}")
    engine = Engine(llm, make_jamba_params(m, cfg["program"]["weight_seed"]),
                    max_len=cfg["program"]["max_tokens"] + n_new)
    engine.calls, engine.kept = [], []      # (prompts, mask, answers) a call
    engine.rng = np.random.default_rng([seed % (1 << 63), 7])
    engine.pick = jax.jit(_pick)
    return engine, m


class System(served_text.System):
    span_names = SPANS

    def __init__(self, cell, seed: int, trace: bool):
        from repro.serving.engine import CachedLLMService

        t0 = time.perf_counter()
        llm_config(cell.config)          # a program without the LLM stops here
        super().__init__(embedder_view(cell), seed, trace)
        self.llm_cell = cell
        cfg, tp = cell.config, cell.traffic
        self.n_new = int(tp["max_new_tokens"])
        engine, self.llm = build_engine(cfg, self.n_new, self.seed)
        self.engine = engine
        self.context = engine.max_len    # attention cache slots a row
        self.calls = engine.calls
        live = self.live
        live.engine = engine
        live.llm_svc = CachedLLMService(
            live.svc.embed_fn, live.cache, engine,
            max_query_len=cfg["program"]["max_tokens"],
            max_new_tokens=self.n_new, telemetry=live.telemetry)
        # every leader bucket up to the batch's, logits kept as in the window
        engine.keep = True
        top = 1 << (self.batch - 1).bit_length()
        for rows in (1 << i for i in range(top.bit_length())):
            texts = [self.traffic.novel() for _ in range(rows)]
            ids, mask = engine.tokenizer.encode_batch(
                texts, cfg["program"]["max_tokens"])
            engine.generate(ids, self.n_new, mask=mask)
        engine.keep = False
        engine.kept[:], self.calls[:] = [], []
        self.window_calls = []           # per window batch: its calls
        self.setup_s["llm"] = time.perf_counter() - t0 - sum(
            self.setup_s.values())
        live.telemetry.tracer.drain()
        self.before = counters.counters(live.cache)
        # the window's full collections and their wall time
        self.collections, self._gc_t0 = [], 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.collections.append(time.perf_counter() - self._gc_t0)

    # -- window ---------------------------------------------------------
    def step(self):
        rows = self.traffic.batches(1)[0]
        # the first call and every record_every-th after it
        self._record = self.n_batches % self.record_every == 0
        self.engine.keep = self._record
        self.n_batches += 1
        k0 = len(self.calls)
        t = time.perf_counter()
        out = self.live.llm_svc.handle(rows)
        dt = time.perf_counter() - t
        self.log.append((rows, out))
        self.window_calls.append((k0, len(self.calls)))
        return dt, len(rows)

    # -- after the window -------------------------------------------------
    def after_window(self):
        engine = self.engine
        engine.keep = False
        gc.callbacks.remove(self._on_gc)
        roots = self.live.telemetry.tracer.roots()
        decode = [s.attrs for root in roots for s in root.walk()
                  if s.name == "generate.decode"]
        self.decode_calls = [(a["padded_to"], a["steps"]) for a in decode]
        self._print_slowest(roots)
        calls = self.calls
        self.prompt_tokens = [int(m.sum()) for _, mask, _ in calls
                              for m in mask]
        # a seeded sample of the kept generations, to the host
        n = min(len(engine.kept), int(self.llm_cell.traffic[
            "sample_generations"]))
        pick = engine.rng.choice(len(engine.kept), n, replace=False)
        self.sampled = []
        for i in sorted(pick):
            k = engine.kept[i]
            prompts, mask, tokens = calls[k["call"]]
            r = k["row"]
            self.sampled.append({
                "prompt": prompts[r][mask[r]].tolist(),
                "answer": tokens[r].tolist(),
                "logits": np.stack([np.asarray(x, np.float32)
                                    for x in k["logits"]])})
        engine.kept[:] = []
        super().after_window()

    def _print_slowest(self, roots):
        """The window's slowest calls, which set the p95: their prefill
        and decode, and their median and longest decode step (between
        two token copies' ends); and the window's full collections."""
        parts = []
        for i, r in enumerate(roots):
            pre, dec = (r.find(n) for n in ("generate.prefill",
                                            "generate.decode"))
            ends = [s.start_s + s.duration_s for s in dec.walk()
                    if s.name == "generate.sync"] if dec else []
            steps = 1e3 * np.diff(ends) if len(ends) > 1 else np.zeros(1)
            parts.append((r.duration_s, i,
                          1e3 * pre.duration_s if pre else 0.0,
                          1e3 * dec.duration_s if dec else 0.0,
                          float(np.median(steps)), float(steps.max())))
        print("bench: median call ms %.1f; slowest (call, ms, prefill ms, "
              "decode ms, median and longest step ms): %s; %d full "
              "collections in the window, %.1f ms" % (
                  1e3 * float(np.median([p[0] for p in parts])),
                  "; ".join("%d %.1f %.1f %.1f %.2f %.2f" % (i, 1e3 * d, *x)
                            for d, i, *x in sorted(parts)[-5:]),
                  len(self.collections), 1e3 * sum(self.collections)),
              file=sys.stderr)

    def layer_context(self) -> dict:
        ctx = super().layer_context()
        ctx.update(llm=self.llm, decode_calls=self.decode_calls,
                   prompt_tokens=self.prompt_tokens, new_tokens=self.n_new,
                   context=self.context)
        return ctx

    # -- the check --------------------------------------------------------
    def check(self, control: bool = False) -> dict:
        readings = super().check(control)
        bad, coal = self._answers()
        readings["bad_answers"] = (bad, self.limits["bad_answers"])
        if coal:
            from harness.weights import make_encoder_params
            from reference import encoder as ref
            texts = sorted({t for pair in coal for t in pair})
            e = ref.embed_texts(make_encoder_params(
                self.m, self.cell.config["program"]["weight_seed"]),
                texts, self.m)
            at = dict(zip(texts, e))
            band = self.threshold - float(self.limits["cos_band"])
            false = sum(float(at[a] @ at[b]) < band for a, b in coal)
            v, lim = readings["false_hits"]
            readings["false_hits"] = (v + int(false), lim)
        gen = self._generations(control)
        readings["logit_gap"] = (gen["f32"], self.limits["logit_gap"])
        readings["bad_tokens"] = (gen["bad_tokens"],
                                  self.limits["bad_tokens"])
        if control:
            readings["control_logit_gap"] = (gen["fp8"],
                                             self.limits["logit_gap"])
        return readings

    def _answers(self):
        """-> (bad answers, [(follower, leader)] of the window's misses)."""
        from reference.encoder import tokenize
        calls = self.calls
        window = self.log[self.n_prefill:self.n_window]
        vocab, max_tokens = self.llm["vocab"], \
            self.llm_cell.config["program"]["max_tokens"]
        bad, coal = 0, []
        for (rows, out), (k0, k1) in zip(window, self.window_calls):
            gens = {}                    # answer -> prompts that gave it
            for prompts, mask, tokens in calls[k0:k1]:
                for p, m, a in zip(prompts, mask, tokens):
                    gens.setdefault(" ".join(map(str, a)), set()).add(
                        tuple(p[m].tolist()))
            miss = [(t, o.response) for t, o in zip(rows, out)
                    if o is not None and not o.cache_hit]
            if not miss:
                continue
            ids, mask = tokenize([t for t, _ in miss], vocab, max_tokens)
            own = [tuple(i[mk].tolist()) for i, mk in zip(ids, mask)]
            for (t, a), p in zip(miss, own):
                if a not in gens:
                    bad += 1
                elif p not in gens[a]:
                    leader = next((u for (u, b), q in zip(miss, own)
                                   if b == a and q in gens[a]), None)
                    if leader is None:
                        bad += 1
                    else:
                        coal.append((t, leader))
        return bad, coal

    def _generations(self, control: bool) -> dict:
        """The sampled generations against the reference: the largest
        relative L2 gap of their logits (``f32``; the fp8 control's
        under ``fp8``), and ``bad_tokens``, the positions whose answer
        token is not the greedy pick of the program's own logits, or
        not the reference's where the reference's top two lie further
        apart than twice the widest gap of one logit (there no error of
        the program's size can change the pick)."""
        from reference import jamba as ref
        if not self.sampled:
            return {"f32": float("inf"), "fp8": 0.0, "bad_tokens": 0}
        params = make_jamba_params(
            self.llm, self.llm_cell.config["program"]["weight_seed"])
        prompts = [s["prompt"] for s in self.sampled]
        answers = np.asarray([s["answer"] for s in self.sampled])
        l_ref = ref.teacher_forced(params, prompts, answers.tolist(),
                                   self.llm)
        l_prog = np.stack([s["logits"] for s in self.sampled])

        def gap(logits):
            return float((np.linalg.norm(logits - l_ref, axis=-1)
                          / np.linalg.norm(l_ref, axis=-1)).max())

        top2 = np.sort(l_ref, axis=-1)[..., -2:]
        clear = top2[..., 1] - top2[..., 0] > 2 * np.abs(
            l_prog - l_ref).max(axis=-1)
        bad = (answers != l_prog.argmax(-1)) | (
            clear & (answers != l_ref.argmax(-1)))
        out = {"f32": gap(l_prog), "bad_tokens": int(bad.sum())}
        if control:
            out["fp8"] = gap(ref.teacher_forced(params, prompts,
                                                answers.tolist(), self.llm,
                                                "fp8"))
        return out
