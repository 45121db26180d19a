"""END-TO-END DRIVER: serve a small LLM with batched requests behind a
semantic cache — the paper's deployment, wired through every layer of
this framework (embedder fine-tune -> cache -> vector store -> serving
engine -> decoder backbone).

    PYTHONPATH=src python examples/serve_with_cache.py \
        --arch granite-moe-3b-a800m --queries 120 --batch 8

Any assigned decoder arch works via --arch (reduced variant on CPU).
Prints the hit/miss trace and the cost accounting the paper's Figure 4
motivates (LLM forward passes saved by the cache).

By default the serving path runs the tiered multi-tenant CacheService
(hot exact tier + warm IVF tier, demotion, admission, response GC);
pass --flat for the paper's bare SemanticCache, --tenants N to
round-robin batches over N isolated logical caches,
--background-rebuild to double-buffer the warm IVF re-cluster off the
hot path (DESIGN.md §7), --learned-admission to refit per-tenant
thresholds/margins online from observed duplicate rates (DESIGN.md
§9), --learned-embedder to fine-tune the embedder itself from pooled
serving feedback and hot-swap it with a versioned shadow re-embed
(DESIGN.md §11), --cold-capacity N to back the warm ring with a
host-RAM cold tier that catches demotions and serves them back through
budgeted fetches + async promotion (DESIGN.md §12).  For serving
several embedders at once through the fused multi-embedder cascade
with learned per-tenant mixture weights, see ``repro.launch.serve
--ensemble E`` (DESIGN.md §13).  Requests flow
through the typed plan/commit
lifecycle (near-identical misses in a batch share one generation) and
the summary prints the protocol's unified stats() snapshot.
"""
import argparse
import time

import jax
import numpy as np

from repro.cache_service import (
    CacheConfig, CacheService, EmbedderRefreshPolicy, LearningConfig,
    TieringConfig,
)
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core import EmbedderTrainer, FinetuneConfig, SemanticCache
from repro.data import HashTokenizer, make_pair_dataset, make_query_stream
from repro.models import init_lm, split
from repro.obs import Telemetry, write_jsonl
from repro.serving import CachedLLMService, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-3b-a800m",
                    choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--queries", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--threshold", type=float, default=0.93)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--no-finetune", action="store_true")
    ap.add_argument("--flat", action="store_true",
                    help="use the paper's flat SemanticCache instead of "
                         "the tiered CacheService")
    ap.add_argument("--tenants", type=int, default=1,
                    help="round-robin request batches over N logical "
                         "tenants (tiered cache only)")
    ap.add_argument("--background-rebuild", action="store_true",
                    help="double-buffer the warm IVF rebuild: k-means "
                         "runs on a shadow index off the hot path and "
                         "maintenance() publishes it between batches")
    ap.add_argument("--learned-admission", action="store_true",
                    help="learn per-tenant thresholds and admission "
                         "margins online from observed duplicate rates "
                         "(maintenance() refits them under hysteresis "
                         "guards, DESIGN.md §9)")
    ap.add_argument("--learned-embedder", action="store_true",
                    help="fine-tune the compact embedder online from "
                         "pooled serving feedback; maintenance() trains "
                         "in the background, gates on held-out eval, and "
                         "hot-swaps with a versioned shadow re-embed "
                         "(DESIGN.md §11)")
    ap.add_argument("--cold-capacity", type=int, default=0,
                    help="host-RAM cold-tier rows behind the warm ring: "
                         "warm evictions demote instead of dropping, "
                         "below-threshold queries fall through to a "
                         "budgeted cold fetch, maintenance() promotes "
                         "re-hot rows back (0 = off; DESIGN.md §12)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the telemetry registry snapshot as "
                         "JSON-lines after the run (DESIGN.md §10.1); "
                         "validate with python -m repro.obs.export "
                         "--validate PATH")
    args = ap.parse_args()
    if args.flat and (args.background_rebuild or args.learned_admission
                      or args.learned_embedder or args.cold_capacity):
        ap.error("--background-rebuild/--learned-admission/"
                 "--learned-embedder/--cold-capacity "
                 "require the tiered CacheService (drop --flat)")

    # --- LLM backend (reduced variant of the assigned arch) -----------
    dec_cfg = get_config(args.arch).reduced()
    print(f"backend: {dec_cfg.name} ({dec_cfg.param_count():,} params)")
    pv, _ = split(init_lm(dec_cfg, jax.random.PRNGKey(0)))
    engine = ServeEngine(dec_cfg, pv, max_len=64)

    # --- cache-side embedder (paper recipe) ---------------------------
    enc_cfg = get_config("modernbert-149m").reduced(vocab_size=4096)
    tok = HashTokenizer(vocab_size=enc_cfg.vocab_size)
    trainer = EmbedderTrainer(enc_cfg, FinetuneConfig(
        epochs=2, batch_size=32, lr=5e-4, max_len=24, margin=0.7))
    if not args.no_finetune:
        print("fine-tuning embedder (online contrastive, clip 0.5)...")
        trainer.fit(make_pair_dataset("medical", 1024, seed=0), tok)

    telemetry = Telemetry()
    if args.flat:
        cache = SemanticCache(capacity=4096, dim=enc_cfg.d_model,
                              threshold=args.threshold,
                              telemetry=telemetry)
    else:
        # smoke-scale refresh policy: trip inside a short stream, with
        # grammar backfill when the pooled pairs run thin (§11)
        refresh = EmbedderRefreshPolicy(
            min_pairs=24, min_class=4, refresh_interval=32,
            synth_domain="medical", synth_min_pairs=128,
            recalibrate=True,
        ) if args.learned_embedder else None
        cache = CacheService(CacheConfig(
            dim=enc_cfg.d_model, threshold=args.threshold,
            admission_margin=0.02, telemetry=telemetry,
            tiering=TieringConfig(
                hot_capacity=512, warm_capacity=4096, n_clusters=32,
                bucket=256, n_probe=4, flush_size=128,
                background_rebuild=args.background_rebuild,
                cold_capacity=args.cold_capacity),
            learning=LearningConfig(
                learned_admission=args.learned_admission,
                learned_embedder=args.learned_embedder,
                embedder_trainer=trainer
                if args.learned_embedder else None,
                embedder_tokenizer=tok
                if args.learned_embedder else None,
                refresh_policy=refresh)))
    svc = CachedLLMService(trainer.make_embed_fn(tok), cache, engine,
                           max_new_tokens=args.max_new_tokens)

    # --- batched serving loop over a repeated-query trace -------------
    stream = make_query_stream("medical", args.queries, seed=11,
                               repeat_frac=0.4)
    texts = [q.text for q in stream]
    t0 = time.perf_counter()
    llm_time = 0.0
    for i in range(0, len(texts), args.batch):
        batch = texts[i:i + args.batch]
        tenant = (i // args.batch) % max(args.tenants, 1)
        t1 = time.perf_counter()
        results = svc.handle(batch, tenant=tenant) if not args.flat \
            else svc.handle(batch)
        dt = time.perf_counter() - t1
        n_hit = sum(r.cache_hit for r in results)
        if i // args.batch < 5:
            for r in results[:2]:
                tag = "HIT " if r.cache_hit else "MISS"
                print(f"  [{tag}] {r.query[:60]!r}")
        print(f"batch {i//args.batch:3d}: {n_hit}/{len(batch)} hits "
              f"({dt*1e3:.0f} ms)")
    total = time.perf_counter() - t0

    # one unified snapshot: serving counters at the top level, the
    # backend's stats_snapshot() sections nested under "backend" (the
    # flat stats() view was removed in v2.0)
    st = svc.stats()
    bk = st["backend"]
    print(f"\n=== serving summary ===")
    print(f"queries: {args.queries}  batches of {args.batch}")
    print(f"cache hits: {st['hits']}  misses: {st['misses']}  "
          f"hit rate: {st['hit_rate']:.1%}")
    print(f"LLM generations: {st['generations']} "
          f"(coalesced duplicate misses: {st['coalesced_misses']})")
    print(f"LLM forward passes saved: {st['hits']} "
          f"({st['hits'] * args.max_new_tokens} decode steps)")
    print(f"wall time: {total:.1f}s  cache occupancy: {cache.occupancy:.1%}")
    if not args.flat:
        print(f"tiers: hot hits {bk['traffic']['hot_hits']}  warm hits "
              f"{bk['traffic']['warm_hits']}  demotions "
              f"{bk['tiers']['demotions']}  "
              f"rebuilds {bk['rebuild']['rebuilds']} "
              f"(background: {bk['rebuild']['shadow_started']}, last "
              f"{bk['rebuild']['last_wall_s'] * 1e3:.0f} ms, total "
              f"{bk['rebuild']['total_wall_s'] * 1e3:.0f} ms)")
        print(f"admission skips: {bk['admission']['skipped']}  "
              f"responses GC'd: {bk['tiers']['evictions']}  live: "
              f"{bk['tiers']['live_responses']}")
        if args.cold_capacity:
            cd = cache.stats_snapshot().tiers["cold"]
            print(f"cold tier: {cd['cold_rows']} rows "
                  f"({cd['cold_occupancy']:.0%}), hits {cd['cold_hits']} "
                  f"from {cd['cold_fetches']} fetches "
                  f"({cd['cold_fetched_rows']} rows shipped, "
                  f"{cd['cold_router_skips']} router skips); promoted "
                  f"{cd['cold_promoted']}, final drops "
                  f"{cd['cold_dropped']}")
        if args.learned_admission:
            lrn = bk["learning"]
            print(f"learned admission: {lrn['refits_applied']} refits "
                  f"from {lrn['feedback_events']} events "
                  f"({lrn['duplicate_events']} duplicates, "
                  f"{lrn['wasted_admissions']} wasted admissions)")
            for t, pol in lrn["learned_policies"].items():
                print(f"  tenant {t}: threshold "
                      f"{pol['threshold']:.3f}  margin "
                      f"{pol['admission_margin']:.3f}")
        if args.learned_embedder:
            cache.maintenance(block=True)   # join an in-flight refresh
            st = svc.stats()
            rf = st["backend"]["refresh"]
            print(f"learned embedder: version {rf['embed_version']} "
                  f"({rf['refreshes_published']} published, "
                  f"{rf['refreshes_rolled_back']} rolled back from "
                  f"{rf['refreshes_started']} started; "
                  f"{rf['pairs_held']} pairs pooled, "
                  f"{rf['stale_version_commits']} stale-version "
                  f"commits)")

    # --- telemetry: stage breakdown + SLO health (DESIGN.md §10) ------
    cache.maintenance(block=True)     # final idle tick: drain SLO gauges
    print("\n=== telemetry (DESIGN.md §10) ===")
    print(f"maintenance calls between batches: {st['maintenance_calls']}")
    stage_h = telemetry.stage_histogram()
    for stage in ("embed", "plan", "cold_fetch", "generate", "commit",
                  "maintenance"):
        agg = stage_h.aggregate(stage=stage)
        if agg.count:
            print(f"  stage {stage:<12} p50 {agg.quantile(0.5) * 1e3:7.2f} "
                  f"ms  mean {agg.mean * 1e3:7.2f} ms  x{agg.count}")
    root = telemetry.tracer.last_root()
    if root is not None:
        print(f"last request span tree: {root.name} "
              f"({root.duration_s * 1e3:.1f} ms) -> "
              f"{' -> '.join(root.stage_names())}")
    if telemetry.health is not None and not args.flat:
        hs = telemetry.health.snapshot()
        for t, s in hs["tenants"].items():
            print(f"  tenant {t}: hit ewma {s['hit']['ewma']:.2f}  "
                  f"dup-admission {s['wasted_admission']['windowed']:.3f}  "
                  f"budget burn {s['budget_burn']:.2f}")
        reb = hs["rebuild"]
        if reb["publishes"]:
            print(f"  rebuild overlap: {reb['overlap_plans_total']} plans "
                  f"during shadow builds, publish stall p99 "
                  f"{reb['stall_p99_s'] * 1e3:.2f} ms")
    if args.metrics_json:
        write_jsonl(args.metrics_json, telemetry.registry.snapshot(),
                    meta={"arch": dec_cfg.name, "queries": args.queries,
                          "flat": bool(args.flat)})
        print(f"metrics -> {args.metrics_json}")


if __name__ == "__main__":
    main()
