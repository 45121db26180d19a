"""Serving launcher: batched generation for any registry arch, with an
optional semantic cache in front (the paper's deployment).

    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi3-mini-3.8b --requests 32 --batch 8 --cache

``--smoke`` (the default) builds the reduced embedder (2 layers,
d_model 128); ``--no-smoke`` serves the published modernbert-149m
widths (22 layers, d_model 768, vocab 50,368) with cache keys at
D=768.  The decoder behind the cache is the ``--arch`` config reduced
to its smoke size, except that ``--no-smoke`` serves a decoder whose
published weights fit half a chip at those widths (``--arch
jamba2-3b``: 28 layers, d_model 2560, bf16); `build_engine` prints
which.
`chip_smoke.py` drives the same construction (`build_stack`).

``--tiered`` swaps the flat SemanticCache for the tiered CacheService;
``--cache-shards N`` then lays its warm tier over an N-device `model`
mesh (local IVF probe per shard + tiny merge, DESIGN.md §8),
``--warm-dtype int8`` scans the warm panel from its quantized form,
``--learned-admission`` turns the static per-tenant operating
points into the online feedback loop (DESIGN.md §9), and
``--learned-embedder`` additionally fine-tunes the compact embedder
from pooled serving feedback in the background, hot-swapping it with a
versioned shadow re-embed of the cached corpus (DESIGN.md §11), and
``--cold-capacity N`` backs the warm ring with an N-row host-RAM cold
tier — warm evictions demote instead of dropping, below-threshold
queries fall through to a budgeted cold fetch, and re-hot rows promote
back up on the idle tick (DESIGN.md §12).  ``--ensemble E`` serves E
embedders through the fused multi-embedder cascade — the fine-tuned
embedder is the pilot, the extra panels are random-projection
embedders, and the feedback loop learns per-tenant mixture weights
(DESIGN.md §13).

``--metrics-json PATH`` dumps the telemetry registry (DESIGN.md §10)
as JSON-lines — one meta line then one line per metric series — after
the run; ``--metrics-interval N`` additionally appends a snapshot
every N batches, so the file holds a time series.  Validate with
``python -m repro.obs.export --validate PATH``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import EmbedderTrainer, FinetuneConfig, SemanticCache
from repro.data import HashTokenizer, make_pair_dataset, make_query_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm, split
from repro.obs import Telemetry, write_jsonl
from repro.serving import CachedLLMService, ServeEngine

# a decoder whose published weights take more than half of one v5e
# chip's 16 GB leaves too little beside the cache: it serves reduced
DECODER_BYTES = 8e9
PROMPT_TOKENS = 32              # the service's max_query_len


def run_scenario(args):
    """--scenario NAME: load the §14.1 trace generators by path (the
    benchmarks tree is not a package) and replay one trace against a
    fresh tiered cache under the trace's logical clock."""
    import importlib.util
    from pathlib import Path
    bench = Path(__file__).resolve().parents[3] / "benchmarks" \
        / "bench_scenarios.py"
    spec = importlib.util.spec_from_file_location("bench_scenarios",
                                                  bench)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if args.scenario not in mod.SCENARIOS:
        raise SystemExit(f"unknown scenario {args.scenario!r}; have "
                         f"{sorted(mod.SCENARIOS)}")
    trace = mod.build(args.scenario, smoke=args.smoke)
    row = mod.replay(trace, conformal=args.conformal)
    print(f"scenario {row['scenario']} ({row['mode']} mode): "
          f"{row['n_queries']} queries over {row['n_steps']} steps")
    print(f"  hit rate {row['hit_rate']:.3f}, false-hit rate "
          f"{row['false_hit_rate']:.4f} (budget "
          f"{row['false_hit_budget']}), stale serves "
          f"{row['stale_serves']}")
    print(f"  plan p50 {row['p50_us_per_row']:.0f} us/row, "
          f"p99 {row['p99_us_per_row']:.0f} us/row "
          f"({row['timed_batches']} timed batches)")
    if row.get("ttl_stamped"):
        print(f"  ttl: {row['ttl_stamped']} stamped, "
              f"{row['expired_masked']} masked, "
              f"{row['expired_reaped']} reaped")
    if row.get("conformal_floors"):
        floors = ", ".join(f"t{t}={v:.3f}"
                           for t, v in sorted(row["conformal_floors"]
                                              .items()))
        print(f"  conformal: {row['hit_audits']} hits audited, "
              f"{row['audited_false_hits']} false; floors {floors}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b",
                    help="decoder behind the cache; always served at its "
                         "reduced (2-layer, d_model 128) smoke size")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced embedder (2 layers, d_model 128, cache "
                         "keys D=128); --no-smoke serves the published "
                         "modernbert-149m widths (22 layers, 768 wide, "
                         "D=768) and, with --scenario, the full trace")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--cache", action="store_true")
    ap.add_argument("--threshold", type=float, default=0.93)
    ap.add_argument("--tiered", action="store_true",
                    help="tiered CacheService instead of the flat "
                         "SemanticCache")
    ap.add_argument("--cache-shards", type=int, default=0,
                    help="shard the warm tier over a model-axis mesh of "
                         "N devices (0 = unsharded; implies --tiered)")
    ap.add_argument("--warm-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="warm-panel scan precision; int8 quantizes the "
                         "warm keys (exact re-score at merge, DESIGN.md "
                         "§8; implies --tiered)")
    ap.add_argument("--learned-admission", action="store_true",
                    help="learn per-tenant thresholds/admission margins "
                         "online from observed duplicate rates "
                         "(DESIGN.md §9; implies --tiered)")
    ap.add_argument("--cold-capacity", type=int, default=0,
                    help="host-RAM cold-tier rows behind the warm ring "
                         "(0 = no cold tier; DESIGN.md §12; implies "
                         "--tiered, incompatible with --cache-shards)")
    ap.add_argument("--warm-block", type=int, default=0,
                    help="stream the fused kernel's warm panel in blocks "
                         "of N rows (0 = whole-panel residency; "
                         "DESIGN.md §12)")
    ap.add_argument("--ensemble", type=int, default=0, metavar="E",
                    help="serve E embedders through the fused multi-"
                         "embedder cascade: the fine-tuned embedder is "
                         "the pilot, panels 1..E-1 are random-projection "
                         "embedders, mixture weights learned per tenant "
                         "(DESIGN.md §13; implies --tiered, incompatible "
                         "with --learned-embedder)")
    ap.add_argument("--learned-embedder", action="store_true",
                    help="refresh the compact embedder online from pooled "
                         "serving feedback and hot-swap it with a "
                         "versioned shadow re-embed (DESIGN.md §11; "
                         "implies --tiered)")
    ap.add_argument("--ttl", type=float, default=0.0, metavar="SECONDS",
                    help="default TTL stamped on every admitted entry "
                         "(0 = never expire); expired entries are masked "
                         "at plan time and reaped on the maintenance "
                         "tick (DESIGN.md §14.2; implies --tiered)")
    ap.add_argument("--conformal", action="store_true",
                    help="per-tenant split-conformal hit calibration: "
                         "serve only above a recency-window quantile of "
                         "observed negative scores, bounding the "
                         "false-hit rate under drift (DESIGN.md §14.3; "
                         "implies --tiered)")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="replay one benchmarks/scenarios.py trace "
                         "against a fresh tiered cache under its logical "
                         "clock and print the scored row (no LLM engine; "
                         "DESIGN.md §14.1) — e.g. drift, ttl_churn")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the telemetry registry snapshot as "
                         "JSON-lines after the run (DESIGN.md §10.1; "
                         "requires --cache)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="with --metrics-json: also append a snapshot "
                         "every N batches (0 = final snapshot only)")
    return ap


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    """Parse and cross-check the launcher's flags (options that need
    the tiered service switch it on)."""
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.scenario:
        return args
    if args.metrics_json and not args.cache:
        ap.error("--metrics-json instruments the cached serving path; "
                 "add --cache")
    if args.cache_shards or args.warm_dtype != "float32" \
            or args.learned_admission or args.learned_embedder \
            or args.cold_capacity or args.warm_block or args.ensemble \
            or args.ttl or args.conformal:
        args.tiered = True
    if args.cold_capacity and args.cache_shards:
        ap.error("--cold-capacity needs the unsharded warm ring; drop "
                 "--cache-shards (DESIGN.md §12)")
    if args.ensemble == 1:
        ap.error("--ensemble needs E >= 2 (a single embedder is the "
                 "default cascade)")
    if args.ensemble and args.learned_embedder:
        ap.error("--ensemble and --learned-embedder are exclusive: the "
                 "§11 refresh re-embeds one key panel, the §13 ensemble "
                 "serves several (swap panels via publish_panel instead)")
    return args


@dataclass
class Stack:
    """Everything `build_stack` constructs for the cached serving path."""
    decoder: ModelConfig
    embedder: ModelConfig
    tokenizer: HashTokenizer
    trainer: EmbedderTrainer
    telemetry: Telemetry
    cache: object                      # CacheService | SemanticCache
    service: CachedLLMService


def decoder_config(arch: str, smoke: bool) -> ModelConfig:
    """The decoder behind the cache: at its published widths and weight
    dtype unless ``smoke``, where those weights take at most
    `DECODER_BYTES` (jamba2-3b's bf16 6.06 GB), else reduced."""
    cfg = get_config(arch)
    nbytes = cfg.param_count() * jax.numpy.dtype(cfg.param_dtype).itemsize
    return cfg.reduced() if smoke or nbytes > DECODER_BYTES else cfg


def build_engine(args) -> Tuple[ModelConfig, ServeEngine]:
    cfg = decoder_config(args.arch, args.smoke)
    pv, _ = split(init_lm(cfg, jax.random.PRNGKey(0)))
    print(f"decoder {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size} ({cfg.param_count():,} "
          f"params, {cfg.param_dtype})")
    return cfg, ServeEngine(cfg, pv,
                            max_len=PROMPT_TOKENS + args.max_new_tokens)


def build_embedder(args) -> Tuple[ModelConfig, HashTokenizer,
                                  EmbedderTrainer]:
    """The cache's embedder, fine-tuned for one epoch on seeded
    synthetic medical pairs; published widths unless ``args.smoke``.
    The published model trains at the paper's learning rate; 5e-4 suits
    only the reduced one, and at 768 wide it collapses every embedding
    into a narrow cone."""
    enc_cfg = get_config("modernbert-149m")
    ft = FinetuneConfig(epochs=1, batch_size=32, max_len=24)
    if args.smoke:
        enc_cfg = enc_cfg.reduced(vocab_size=4096)
        ft = dataclasses.replace(ft, lr=5e-4)
    print(f"embedder {enc_cfg.name}: {enc_cfg.n_layers} layers, d_model "
          f"{enc_cfg.d_model}, d_ff {enc_cfg.d_ff}, vocab "
          f"{enc_cfg.vocab_size} ({enc_cfg.param_count():,} params); "
          f"cache keys D={enc_cfg.d_model}")
    tok = HashTokenizer(vocab_size=enc_cfg.vocab_size)
    trainer = EmbedderTrainer(enc_cfg, ft)
    fit = trainer.fit(make_pair_dataset("medical", 512, seed=0), tok)
    print(f"fine-tuned the embedder: {fit['steps']} steps in "
          f"{fit['train_seconds']:.1f}s")
    return enc_cfg, tok, trainer


def build_cache(args, enc_cfg: ModelConfig, trainer: EmbedderTrainer,
                tok: HashTokenizer, telemetry: Telemetry):
    """The flat SemanticCache, or the tiered CacheService the flags
    describe."""
    if not args.tiered:
        return SemanticCache(capacity=4096, dim=enc_cfg.d_model,
                             threshold=args.threshold, telemetry=telemetry)
    from repro.cache_service import (
        CacheConfig, CacheService, EmbedderRefreshPolicy, EnsembleConfig,
        LearningConfig, ShardingConfig, StalenessConfig, TieringConfig,
    )
    from repro.launch.mesh import make_cache_mesh
    mesh = make_cache_mesh(args.cache_shards) if args.cache_shards \
        else None
    # smoke-scale refresh policy: trip the trigger inside a short
    # stream, backfill thin splits from the medical grammar (§11)
    refresh = EmbedderRefreshPolicy(
        min_pairs=24, min_class=4, refresh_interval=32,
        synth_domain="medical", synth_min_pairs=128,
        recalibrate=True,
    ) if args.learned_embedder else None
    cache = CacheService(CacheConfig(
        dim=enc_cfg.d_model, threshold=args.threshold,
        telemetry=telemetry,
        tiering=TieringConfig(hot_capacity=512, warm_capacity=4096,
                              n_clusters=32, bucket=256,
                              warm_dtype=args.warm_dtype,
                              warm_block=args.warm_block or None,
                              cold_capacity=args.cold_capacity),
        sharding=ShardingConfig(mesh=mesh),
        learning=LearningConfig(
            learned_admission=args.learned_admission,
            conformal=args.conformal,
            learned_embedder=args.learned_embedder,
            embedder_trainer=trainer if args.learned_embedder else None,
            embedder_tokenizer=tok if args.learned_embedder else None,
            refresh_policy=refresh),
        ensemble=EnsembleConfig(embedders=args.ensemble or None),
        staleness=StalenessConfig(default_ttl=args.ttl or None)))
    caps = cache.capabilities()
    print(f"tiered cache: warm shards "
          f"{cache.warm_shards if caps.warm_sharded else 0}, "
          f"warm dtype {caps.warm_dtype}, learned admission "
          f"{'on' if caps.learned_admission else 'off'}, "
          f"learned embedder "
          f"{'on' if caps.learned_embedder else 'off'}, "
          f"cold tier {args.cold_capacity if caps.cold_tier else 0} "
          f"rows, ensemble "
          f"{f'E={caps.ensemble}' if caps.ensemble else 'off'}, "
          f"ttl {args.ttl or 'off'}, conformal "
          f"{'on' if caps.conformal else 'off'}")
    return cache


def build_stack(args) -> Stack:
    """Construct the cached serving path: decoder, fine-tuned embedder,
    cache backend and the `CachedLLMService` in front of them."""
    cfg, engine = build_engine(args)
    enc_cfg, tok, trainer = build_embedder(args)
    telemetry = Telemetry()
    cache = build_cache(args, enc_cfg, trainer, tok, telemetry)
    embed_fn = trainer.make_embed_fn(tok)
    if args.ensemble:
        # pilot = the fine-tuned embedder; the extra panels are cheap
        # independent views (random projections, distinct seeds) so the
        # fused cascade and the weight learner see genuine diversity
        from repro.core.embedders import RandomProjectionEmbedder
        extras = [RandomProjectionEmbedder(dim=enc_cfg.d_model,
                                           seed=101 + e)
                  for e in range(args.ensemble - 1)]
        pilot_fn = embed_fn

        def embed_fn(texts):
            panels = [pilot_fn(texts)] + [np.asarray(e.embed(texts))
                                          for e in extras]
            return np.stack(panels, axis=1)        # (B, E, D)
    svc = CachedLLMService(embed_fn, cache, engine,
                           max_query_len=PROMPT_TOKENS,
                           max_new_tokens=args.max_new_tokens)
    return Stack(decoder=cfg, embedder=enc_cfg,
                 tokenizer=tok, trainer=trainer, telemetry=telemetry,
                 cache=cache, service=svc)


def serve_stream(args, stack: Stack) -> float:
    """Serve the seeded medical query stream in batches through
    ``stack.service``; returns the wall seconds."""
    def dump_metrics(batch_idx, append):
        write_jsonl(args.metrics_json, stack.telemetry.registry.snapshot(),
                    meta={"arch": stack.decoder.name, "batch": batch_idx,
                          "tiered": args.tiered}, append=append)

    stream = [q.text for q in make_query_stream("medical", args.requests,
                                                seed=1, repeat_frac=0.4)]
    t0 = time.perf_counter()
    wrote = False
    for i in range(0, len(stream), args.batch):
        stack.service.handle(stream[i:i + args.batch])
        b = i // args.batch
        if args.metrics_json and args.metrics_interval \
                and (b + 1) % args.metrics_interval == 0:
            dump_metrics(b, append=wrote)
            wrote = True
    stack.cache.maintenance(block=True)  # final idle tick: drain SLO gauges
    if args.metrics_json:
        dump_metrics(args.requests // args.batch, append=wrote)
    return time.perf_counter() - t0


def report(args, stack: Stack, wall: float) -> None:
    """Print the serving summary: hit rate, stage latencies and the
    enabled subsystems' counters."""
    svc, cache = stack.service, stack.cache
    print(f"{args.requests} requests in {wall:.1f}s; "
          f"hit rate {svc.hit_rate:.1%} "
          f"({int(svc.stats()['hits'])} LLM calls saved)")
    stage_h = stack.telemetry.stage_histogram()
    for stage in ("embed", "plan", "cold_fetch", "generate", "commit",
                  "maintenance"):
        agg = stage_h.aggregate(stage=stage)
        if agg.count:
            print(f"  stage {stage:<12} p50 {agg.quantile(0.5) * 1e3:7.2f} "
                  f"ms  mean {agg.mean * 1e3:7.2f} ms  x{agg.count}")
    if args.cold_capacity:
        cd = cache.stats_snapshot().tiers["cold"]
        print(f"cold tier: {cd['cold_rows']} rows "
              f"({cd['cold_occupancy']:.0%} of {args.cold_capacity}), "
              f"{cd['cold_hits']} hits from {cd['cold_fetches']} fetches "
              f"({cd['cold_fetched_rows']} rows shipped, "
              f"{cd['cold_router_skips']} router skips); "
              f"{cd['cold_promoted']} promoted back to warm, "
              f"{cd['cold_dropped']} final drops")
    if args.ensemble:
        ws = cache.policies.weights_state()
        print(f"ensemble: {cache.capabilities().ensemble} embedders, "
              f"{len(ws)} tenant(s) with learned mixture weights")
    # backend sections nest under svc.stats()["backend"] since the flat
    # stats() view was removed in v2.0
    if args.learned_admission:
        lrn = svc.stats()["backend"]["learning"]
        print(f"learned admission: {lrn['refits_applied']} refits from "
              f"{lrn['feedback_events']} events "
              f"({lrn['duplicate_events']} duplicates, "
              f"{lrn['wasted_admissions']} wasted admissions); "
              f"policies {lrn['learned_policies']}")
    if args.learned_embedder:
        bk = svc.stats()["backend"]
        rf = bk["refresh"]
        print(f"learned embedder: version {rf['embed_version']} "
              f"({rf['refreshes_published']} published, "
              f"{rf['refreshes_rolled_back']} rolled back from "
              f"{rf['refreshes_started']} started; "
              f"{rf['pairs_held']} pairs pooled, "
              f"{rf['stale_version_commits']} stale-version commits; "
              f"recalibrated threshold "
              f"{rf['recalibrated_threshold']})")
    if args.ttl:
        stl = cache.stats_snapshot().tiers["staleness"]
        print(f"ttl: {stl['ttl_stamped']} stamped, "
              f"{stl['expired_masked']} masked at plan time, "
              f"{stl['expired_reaped']} reaped")
    if args.conformal:
        cs = cache.stats_snapshot().learning["conformal"]
        print(f"conformal: {cs['hit_audits']} hit audits "
              f"({cs['audited_false_hits']} false), "
              f"{len(cs['tenants'])} tenant window(s)")
    if args.metrics_json:
        print(f"metrics -> {args.metrics_json}")


def generate_only(args) -> None:
    """No cache: batched generation of random prompts."""
    cfg, engine = build_engine(args)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(0, args.requests, args.batch):
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, 16)).astype(np.int32)
        res = engine.generate(prompts, args.max_new_tokens)
        print(f"batch {i//args.batch}: generated "
              f"{res.tokens.shape[1]} tokens x {res.tokens.shape[0]}")
    print(f"total {time.perf_counter() - t0:.1f}s")


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}")
    if args.scenario:
        return run_scenario(args)
    if not args.cache:
        return generate_only(args)
    stack = build_stack(args)
    report(args, stack, serve_stream(args, stack))


if __name__ == "__main__":
    main()
