"""Tiered cache vs flat brute force at production corpus sizes, the
served jnp cascade vs the Pallas kernel, replicated vs sharded warm
tier, fp32 vs int8 warm panel.

Flat exact lookup is O(N·D) per query; the tiered cascade is
O(N_hot·D + (K + n_probe·bucket)·D) — at 64k+ entries the warm IVF tier
probes ~6% of the corpus.  This bench builds a clustered corpus
(paraphrase groups, the cache's actual workload) at 16k / 64k / 256k
entries, serves the same query mix through every path, and reports
per-query latency plus the cascade's recall against the exact hit set
at the operating threshold.

Cascade paths compared per size:

  * ``cascade_unfused``       — the served jnp cascade
    (`tiers.cascade_query`, every configuration's lookup).
  * ``cascade_fused_kernel``  — the Pallas kernel forced on
    (interpret mode off-TPU; correctness-path timing, not the CPU
    production path).
  * ``cascade_int8``          — the warm panel scanned from its int8
    symmetric quantization, selected rows re-scored exactly
    (DESIGN.md §8); recall must stay within 0.5% of fp32.
  * ``cascade_sharded``       — the warm tier split over every visible
    device (`model` mesh axis, one local IVF per shard, per-shard
    probes ``n_probe/shards``); the cross-shard collective is the
    (Q, k·shards) candidate merge, reported as ``gather_cols`` —
    compare with ``n``.  shard_map-vs-oracle parity is asserted
    bit-exact.
  * ``cascade_sharded_int8``  — both together.

The fp32 kernel and the served cascade are asserted to produce the
identical hit set (bit-exact parity); the int8 rows assert recall
within 0.5% of fp32 instead (quantization may legitimately flip
candidates inside the error bound).  At the 256k tier the sharded p50
is expected to beat the replicated p50 — asserted on real
multi-device backends, a stderr warning on CPU where "devices" are
threads contending for the same cores.  Set
``BENCH_TIERED_SIZES=16384,65536`` to override the size sweep.

The ``tiered/ensemble/*`` rows time the fused multi-embedder cascade
(DESIGN.md §13): one pilot-routed kernel pass over E stacked key
panels with the weighted fused score computed in-VMEM, vs the
single-embedder cascade it must cost at most 1.6x of (the sequential
alternative costs ~E x).  Fused recall is hard-asserted at or above
the best single embedder's exact recall, the forced kernel is asserted
bit-exact against the served E-panel jnp cascade, and the
``weights_uniform`` / ``weights_learned`` rows run the per-tenant
mixture-weight refit (ridge on per-embedder score/duplicate events)
against frozen uniform weights on a drifting stream.  Override with
``BENCH_ENSEMBLE_SIZES`` / ``BENCH_ENSEMBLE_E``; ``--smoke`` runs
E=2 at 16k.

Platform-conditional asserts (sharded-beats-replicated at 256k, the
fused-ensemble latency bound) are recorded in the JSON as
``checked_asserts`` / ``skipped_asserts`` so the trajectory gate can
verify each one was enforced — or legally skipped on CPU — rather
than silently absent.

Every row also lands in a machine-readable ``BENCH_cascade.json``
(default ``results/BENCH_cascade.json``, override with
``BENCH_CASCADE_JSON``; set it empty to skip writing) so future PRs
have a perf trajectory to diff against — CI enforces the diff via
``scripts/check_bench_trajectory.py`` (recall must not regress vs the
committed baseline, p50 ratios bounded on a matching fleet).

The ``tiered/cold/*`` rows grow the corpus past device memory
(DESIGN.md §12): the device keeps a fixed hot+warm slice while the
rest of the corpus lives only in the host-RAM cold tier (int8 panel,
coarse routing, budgeted fetch + exact device re-score).  At each
cold size — 1M rows by default, ``BENCH_COLD_SIZES`` to override,
64k under ``--smoke`` — a warm-only service and a cold-enabled
service share byte-identical device states, and the bench
hard-asserts the subsystem's reason to exist: at equal device
memory, cold-enabled recall is *strictly* above warm-only recall.
The ``cold_enabled`` row also carries the cold hit rate and fetch
accounting, ``promotion`` times one maintenance-tick drain of queued
re-hot rows, and ``tiered/cold/p50_ratio`` bounds the overhead the
cold path adds at a warm-only-feasible size (where every query is
answerable on-device, the router should decline almost every fetch).

The ``admission_fixed`` / ``admission_learned`` rows run a drifting
paraphrase stream through two otherwise-identical CacheServices — one
frozen at the static operating point, one with the online feedback
loop (DESIGN.md §9) — and hard-assert the loop's claim: duplicate
admissions drop, probe recall holds, the false-hit budget holds.

The ``embedder_frozen`` / ``embedder_refreshed`` rows do the same for
the online embedder refresh (DESIGN.md §11): two services share one
general-purpose (quora-pretrained) compact encoder; one runs the
maintenance-driven refresh cycle — contrastive fine-tune on pooled
serving pairs with synthetic backfill, eval gate, shadow re-embed,
versioned hot swap with threshold recalibration — between two phases
of a drifting-topic medical stream.  Hard asserts: the refreshed
service beats the frozen one on
both hit precision and hit recall over the drifted phase, the publish
happened (``embed_version >= 1``), and overlap recall through the hot
swap is exactly 1.0 (no committed entry is lost by the re-embed).

Rebuild-stall rows (``serve_inline_rebuild`` / ``serve_bg_rebuild``)
time a serving loop — plan over the live CacheService each tick — in
which one tick triggers the demotion flush + IVF re-cluster: inline
mode eats the whole k-means on that tick (it shows up as the lookup
p99), background mode double-buffers it onto a shadow index and the
p99 stays at lookup scale.  Like the flush+rebuild row, these are
skipped above 64k unless ``BENCH_TIERED_SIZES`` opts in explicitly
(the 256k rebuild alone takes minutes on 2 CPU cores).

The ``tiered/serve/stage_*`` rows read the per-stage latency
histograms (plan / commit / maintenance) straight off the telemetry
registry for a small serving pass (DESIGN.md §10.1), and
``tiered/serve/telemetry_overhead`` hard-asserts that running with the
registry + tracer live costs < 2% extra serving p50 over the same pass
with ``Telemetry.disabled()``.

    PYTHONPATH=src python -m benchmarks.run tiered
    PYTHONPATH=src python -m benchmarks.bench_tiered_cache --smoke
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_derived, timed
from repro.cache_service import (
    CacheRequest, CacheService, ColdRoutingPolicy, EmbedderRefreshPolicy,
    FeedbackConfig, tiers,
)
from repro.configs import get_config
from repro.core import EmbedderTrainer, FinetuneConfig
from repro.core import store as store_lib
from repro.data import HashTokenizer, make_pair_dataset
from repro.data.corpora import DOMAINS, render_query
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.obs import Telemetry
from repro.obs.health import check_overhead_budget

HOT = 2048                 # recent-traffic slice held in the hot tier
DIM = 64
N_PROBE = 4
Q = 128
THRESHOLD = 0.9
SEED = 3
# size -> (n_clusters, bucket, kmeans_iters); per-cluster occupancy is
# held near bucket/2 so the inverted lists never overflow
SIZES = {
    1 << 12: (16, 256, 2),      # --smoke / CI tier
    1 << 14: (128, 256, 4),
    1 << 16: (256, 512, 4),
    1 << 18: (512, 1024, 2),
}
DEFAULT_SIZES = [1 << 14, 1 << 16, 1 << 18]
# maintenance-heavy rows (flush+rebuild, rebuild-stall serving) only
# run at or below this size unless BENCH_TIERED_SIZES opts in
MAINT_MAX = 1 << 16
# cold-tier rows: the device keeps this fixed hot+warm slice while the
# rest of the corpus lives only in host RAM (DESIGN.md §12)
COLD_HOT = 1 << 10
COLD_WARM = 1 << 14
COLD_DEFAULT_SIZES = [1 << 20]     # 1M-row corpus; --smoke drops to 64k
# fused multi-embedder ensemble rows (DESIGN.md §13): one kernel pass
# over E stacked key panels, routed on the pilot embedder's centroids.
# The p50 target vs the sequential E-pass alternative is a bandwidth
# claim about accelerator dispatch, so it is hard-asserted off-CPU and
# recorded as a *structured* skip on CPU (see _assert_skipped)
ENS_DEFAULT_SIZES = [1 << 16]
ENS_DEFAULT_E = 3
ENS_MAX_P50_RATIO = 1.6            # fused E-panel p50 vs single-panel p50
# the ensemble operating point sits below the single-embedder one: a
# duplicate one embedder misses scores ((E-1)*0.98 + 0.66)/E fused —
# above this threshold for every E >= 2, while the blind panel's 0.66
# stays below it (the workload _ens_queries builds)
ENS_THRESHOLD = 0.72


def _ensemble_sizes():
    env = os.environ.get("BENCH_ENSEMBLE_SIZES")
    if env is None:
        return list(ENS_DEFAULT_SIZES)
    return [int(s) for s in env.split(",") if s.strip()]


def _ensemble_e():
    return int(os.environ.get("BENCH_ENSEMBLE_E", ENS_DEFAULT_E))


# Platform-conditional asserts.  A claim that only holds on real
# accelerator fleets (sharded beats replicated, fused-ensemble beats
# sequential) used to degrade to a stderr warning on CPU — invisible
# to the trajectory gate, indistinguishable from the assert site being
# deleted.  Every such site now records itself here, and the lists
# land in BENCH_cascade.json (``checked_asserts`` / ``skipped_asserts``)
# so scripts/check_bench_trajectory.py can verify each applicable
# assert was either enforced or legally skipped (CPU only).
_ASSERTS = {"checked": [], "skipped": []}


def _assert_checked(name):
    _ASSERTS["checked"].append(name)


def _assert_skipped(name, reason):
    _ASSERTS["skipped"].append({"name": name, "reason": reason})
    print(f"WARNING: skipped assert {name}: {reason}", file=sys.stderr)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _corpus(rng, n_total, n_clusters):
    """Clustered keys: paraphrase groups around n_clusters centroids."""
    per = n_total // n_clusters
    cents = _unit(rng.standard_normal((n_clusters, DIM)).astype(np.float32))
    keys = np.repeat(cents, per, axis=0)
    return _unit(keys + 0.15 * rng.standard_normal(keys.shape
                                                   ).astype(np.float32))


def _states(keys, n_clusters, bucket, iters):
    """Build flat / hot / warm states directly (bulk load, not the
    sequential insert path — this bench times lookups, not fills)."""
    n = len(keys)
    vids = jnp.arange(n, dtype=jnp.int32)
    flat = store_lib.init_store(n, DIM)._replace(
        keys=jnp.asarray(keys), valid=jnp.ones((n,), bool), value_ids=vids)

    warm_n = n - HOT
    warm = tiers.init_warm(warm_n, DIM, n_clusters, bucket)._replace(
        keys=jnp.asarray(keys[:warm_n]),
        valid=jnp.ones((warm_n,), bool),
        tenants=jnp.zeros((warm_n,), jnp.int32),
        value_ids=vids[:warm_n],
        write_seq=jnp.arange(1, warm_n + 1, dtype=jnp.int32),
        total=jnp.asarray(warm_n, jnp.int32))
    warm = jax.jit(partial(tiers.warm_rebuild, iters=iters, seed=SEED))(warm)
    warm = tiers.requantize(warm)       # int8 panel for the quantized rows

    hot = tiers.init_hot(HOT, DIM)._replace(
        keys=jnp.asarray(keys[warm_n:]),
        valid=jnp.ones((HOT,), bool),
        tenants=jnp.zeros((HOT,), jnp.int32),
        last_used=jnp.arange(1, HOT + 1, dtype=jnp.int32),
        value_ids=vids[warm_n:],
        clock=jnp.asarray(HOT, jnp.int32))
    return flat, hot, warm


def _sharded_warm(keys, n_clusters, bucket, iters, shards, mesh):
    """Stacked warm tier over the same rows as the replicated warm
    (truncated to a shard-divisible count), one local IVF per shard,
    laid out on the mesh so lookups read resident shards."""
    warm_n = ((len(keys) - HOT) // shards) * shards
    cap = warm_n // shards
    k_local = max(n_clusters // shards, 1)
    sw = tiers.init_warm_sharded(shards, cap, DIM, k_local, bucket)._replace(
        keys=jnp.asarray(keys[:warm_n]).reshape(shards, cap, DIM),
        valid=jnp.ones((shards, cap), bool),
        tenants=jnp.zeros((shards, cap), jnp.int32),
        value_ids=jnp.arange(warm_n, dtype=jnp.int32).reshape(shards, cap),
        write_seq=jnp.broadcast_to(
            jnp.arange(1, cap + 1, dtype=jnp.int32), (shards, cap)),
        total=jnp.full((shards,), cap, jnp.int32))
    sw = jax.jit(partial(tiers.warm_rebuild_sharded, iters=iters,
                         seed=SEED))(sw)
    return tiers.place_warm_sharded(tiers.requantize(sw), mesh)


def _queries(rng, keys):
    """Half near-duplicates of random corpus entries, half novel."""
    idx = rng.choice(len(keys), Q // 2, replace=False)
    pos = _unit(keys[idx] + 0.05 * rng.standard_normal(
        (Q // 2, DIM)).astype(np.float32))
    neg = _unit(rng.standard_normal((Q // 2, DIM)).astype(np.float32))
    return jnp.asarray(np.concatenate([pos, neg]))


def _sizes():
    env = os.environ.get("BENCH_TIERED_SIZES")
    if not env:
        return list(DEFAULT_SIZES)
    return [int(s) for s in env.split(",") if s.strip()]


def _cold_sizes():
    env = os.environ.get("BENCH_COLD_SIZES")
    if env is None:
        return list(COLD_DEFAULT_SIZES)
    return [int(s) for s in env.split(",") if s.strip()]


def _maintenance_rows_enabled(n_total):
    return n_total <= MAINT_MAX or bool(os.environ.get("BENCH_TIERED_SIZES"))


def _timed_p50(fn, repeats: int = 7):
    """(p50_us, mean_us) over per-call wall times (after one warmup)."""
    fn()
    lat = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t0)
    lat = np.asarray(lat) * 1e6
    return float(np.percentile(lat, 50)), float(lat.mean())


def _recall(res, exact_hit):
    tier_hit = np.asarray(res.hit)
    recall = float((tier_hit & exact_hit).sum() / max(exact_hit.sum(), 1))
    spurious = int((tier_hit & ~exact_hit).sum())
    return recall, spurious


def _bench_one_size(n_total):
    n_clusters, bucket, iters = SIZES.get(
        n_total, (max(n_total // 512, 16), 1024, 2))
    tag = f"tiered/{n_total // 1024}k"
    rng = np.random.default_rng(SEED)
    keys = _corpus(rng, n_total, n_clusters)
    flat, hot, warm = _states(keys, n_clusters, bucket, iters)
    q = _queries(rng, keys)
    tenants = jnp.zeros((Q,), jnp.int32)
    thresholds = jnp.full((Q,), THRESHOLD, jnp.float32)

    flat_fn = jax.jit(lambda st, qq: store_lib.query(st, qq, THRESHOLD, 1))
    paths = {
        "cascade_unfused": jax.jit(partial(
            tiers.cascade_query, k=1, n_probe=N_PROBE, tail=0)),
        "cascade_fused_kernel": jax.jit(partial(
            tiers.cascade_query, k=1, n_probe=N_PROBE, tail=0,
            use_kernel=True)),
        "cascade_int8": jax.jit(partial(
            tiers.cascade_query, k=1, n_probe=N_PROBE, tail=0,
            quantized=True)),
    }

    exact = flat_fn(flat, q)
    jax.block_until_ready(exact)
    exact_hit = np.asarray(exact.hit)
    _, us_flat = timed(
        lambda: jax.block_until_ready(flat_fn(flat, q)), repeats=5)
    yield f"{tag}/flat_bruteforce", us_flat / Q, {
        "n": n_total, "us_per_query": us_flat / Q,
        "hits": int(exact_hit.sum())}

    results, speedups, recalls, p50s = {}, {}, {}, {}
    for name, fn in paths.items():
        res = fn(hot, warm, q, tenants, thresholds)
        jax.block_until_ready(res)
        results[name] = res
        p50, us = _timed_p50(
            lambda fn=fn: jax.block_until_ready(
                fn(hot, warm, q, tenants, thresholds)))
        p50s[name] = p50
        recall, spurious = _recall(res, exact_hit)
        recalls[name] = recall
        speedup = speedups[name] = us_flat / max(us, 1e-9)
        yield f"{tag}/{name}", us / Q, {
            "n": n_total, "us_per_query": us / Q, "p50_us": p50,
            "recall_at_thr": recall, "spurious_hits": spurious,
            "speedup_vs_flat": speedup}
        if name == "cascade_int8":
            # quantized selection may flip candidates inside the error
            # bound; the budget is 0.5% of the fp32 recall
            assert recall >= recalls["cascade_unfused"] - 0.005, \
                f"{tag}/{name} int8 recall {recall} dropped > 0.5% below " \
                f"fp32 {recalls['cascade_unfused']}"
        else:
            assert recall >= 0.95, f"{tag}/{name} recall {recall} < 0.95"

    # the cascade only pays off once the corpus dwarfs the probed slice;
    # judge only the production dispatches — the forced interpret-mode
    # kernel is a correctness path and must not mask a regression here
    if n_total >= 1 << 16:
        prod = {n: s for n, s in speedups.items()
                if n != "cascade_fused_kernel"}
        assert max(prod.values()) > 1.0, \
            f"{tag}: no production cascade path beats flat ({prod})"

    # no recall regression: the fp32 kernel reproduces the served
    # cascade bit-exactly (scores, ids, hit set); the int8 row is
    # excluded — its parity budget is the 0.5% recall assert above
    base = results["cascade_unfused"]
    for field in tiers.CascadeResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base, field)),
            np.asarray(getattr(results["cascade_fused_kernel"], field)),
            err_msg=f"{tag}/cascade_fused_kernel diverges from the "
                    f"served cascade on {field}")

    yield from _bench_sharded(tag, n_total, keys, hot, q, tenants,
                              thresholds, n_clusters, bucket, iters,
                              exact_hit, recalls, p50s)

    # amortised maintenance: one demotion flush + one IVF rebuild
    # (skipped at 256k by default — the rebuild alone takes minutes on
    # 2 CPU cores; BENCH_TIERED_SIZES opts in explicitly)
    if _maintenance_rows_enabled(n_total):
        dem_fn = jax.jit(partial(tiers.demote_coldest, m=512))
        app_fn = jax.jit(tiers.warm_append)
        reb_fn = jax.jit(partial(tiers.warm_rebuild, iters=iters, seed=SEED))

        def flush_and_rebuild():
            h2, dem = dem_fn(hot)
            w2, _ = app_fn(warm, dem)
            return jax.block_until_ready(reb_fn(w2))

        flush_and_rebuild()
        _, us_maint = timed(flush_and_rebuild, repeats=3)
        yield f"{tag}/flush+rebuild", us_maint, {
            "flush_size": 512, "n_warm": n_total - HOT,
            "clusters": n_clusters}
        yield from _bench_rebuild_stall(n_total, n_clusters, bucket, iters)


def _bench_sharded(tag, n_total, keys, hot, q, tenants, thresholds,
                   n_clusters, bucket, iters, exact_hit, recalls, p50s):
    """Replicated-vs-sharded rows: the warm tier split over every
    visible device, per-shard jnp cascade, (Q, k·shards) merge."""
    shards = len(jax.devices())
    mesh = make_host_mesh(1, shards)
    swarm = _sharded_warm(keys, n_clusters, bucket, iters, shards, mesh)
    # split the probe budget across shards but keep >= 2 probes of
    # slack per local IVF (a top-1-only probe has no tolerance for
    # centroid misranking on noisy near-duplicates), clamped to the
    # per-shard cluster count
    k_local = max(n_clusters // shards, 1)
    probe_local = min(k_local, max(N_PROBE // shards, 2))
    topk = 1           # shared by the lookup and the gather_cols metric
    sharded_paths = {
        "cascade_sharded": {},
        "cascade_sharded_int8": {"quantized": True},
    }
    for name, kw in sharded_paths.items():
        fn = jax.jit(partial(tiers.cascade_query, k=topk,
                             n_probe=probe_local, tail=0, mesh=mesh, **kw))
        res = fn(hot, swarm, q, tenants, thresholds)
        jax.block_until_ready(res)
        # bit-exact parity of the distributed schedule against its
        # single-device oracle (per-shard jnp cascade + stacked
        # merge) — the sharded analogue of the kernel parity assert
        oracle = jax.jit(partial(tiers.cascade_query, k=topk,
                                 n_probe=probe_local, tail=0, **kw))(
            hot, swarm, q, tenants, thresholds)
        for field in tiers.CascadeResult._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(oracle, field)),
                np.asarray(getattr(res, field)),
                err_msg=f"{tag}/{name} diverges from the sharded oracle "
                        f"on {field}")
        p50, us = _timed_p50(
            lambda fn=fn: jax.block_until_ready(
                fn(hot, swarm, q, tenants, thresholds)))
        recall, spurious = _recall(res, exact_hit)
        fp32_ref = recalls["cascade_unfused"]
        yield f"{tag}/{name}", us / Q, {
            "n": n_total, "us_per_query": us / Q, "p50_us": p50,
            "recall_at_thr": recall, "spurious_hits": spurious,
            "shards": shards, "n_probe_local": probe_local,
            "gather_cols": topk * shards,     # the (Q, k·shards) merge
        }
        if "int8" in name:
            assert recall >= fp32_ref - 0.005, \
                f"{tag}/{name} int8 recall {recall} dropped > 0.5% below " \
                f"fp32 {fp32_ref}"
        else:
            assert recall >= 0.95, f"{tag}/{name} recall {recall} < 0.95"
            # the scale claim: at 256k the per-shard slices + tiny merge
            # must beat the replicated cascade.  Hard-assert on real
            # accelerator fleets; on CPU the "devices" are host threads
            # fighting for the same cores, so the claim is recorded as
            # a structured skip the trajectory gate can verify.
            if n_total >= 1 << 18 and shards > 1:
                aname = f"{tag}/sharded_p50_beats_replicated"
                rep_p50 = p50s["cascade_unfused"]
                if jax.default_backend() == "cpu":
                    _assert_skipped(
                        aname, "cpu backend: shards are host threads "
                        "contending for the same cores"
                        + ("" if p50 < rep_p50 else
                           f" (and sharded p50 {p50:.0f}us did not beat "
                           f"replicated {rep_p50:.0f}us here)"))
                else:
                    _assert_checked(aname)
                    assert p50 < rep_p50, \
                        f"{tag}: sharded p50 {p50:.0f}us does not beat " \
                        f"replicated p50 {rep_p50:.0f}us over " \
                        f"{shards} shards"


def _ens_corpus(rng, n_total, n_clusters, e):
    """E correlated key panels over one clustered latent corpus — the
    same paraphrase groups seen through E different embedders, each
    with its own observation noise: (n, E, D)."""
    z = _corpus(rng, n_total, n_clusters)
    return np.stack(
        [_unit(z + 0.1 * rng.standard_normal(z.shape).astype(np.float32))
         for _ in range(e)], 1)


def _ens_queries(rng, panels):
    """Half near-duplicates, half novel.  Each near-duplicate is a
    tight paraphrase of one corpus row on every panel except one:
    panel (i mod E) is corrupted toward noise — the embedder that
    "missed" this paraphrase (cos ~0.66, below the ensemble operating
    point) while the others stay confident (cos ~0.98).  Every single
    embedder therefore misses ~1/E of the duplicates; the fused score
    keeps all of them above ENS_THRESHOLD with deterministic margin —
    the ensemble claim as a workload, not a statistical accident."""
    n, e, _ = panels.shape
    idx = rng.choice(n, Q // 2, replace=False)
    base = panels[idx]
    pos = _unit(base + 0.0254 * rng.standard_normal(
        base.shape).astype(np.float32))
    noisy = _unit(base + 0.142 * rng.standard_normal(
        base.shape).astype(np.float32))
    rows = np.arange(Q // 2)
    pos[rows, rows % e] = noisy[rows, rows % e]
    neg = _unit(rng.standard_normal((Q // 2, e, DIM)).astype(np.float32))
    return np.concatenate([pos, neg]).astype(np.float32)


def _ens_exact(panels, qp, weights):
    """Host-exact per-embedder best cosine (Q, E) and fused best (Q,)
    over the full corpus, chunked like _exact_hit_mask."""
    nq, e = qp.shape[0], qp.shape[1]
    best_e = np.full((nq, e), -1.0, np.float32)
    best_f = np.full(nq, -2.0, np.float32)
    for lo in range(0, len(panels), 1 << 16):
        blk = panels[lo:lo + (1 << 16)]
        cos = np.einsum("qed,bed->qbe", qp, blk)
        best_e = np.maximum(best_e, cos.max(axis=1))
        best_f = np.maximum(
            best_f, (cos * weights[:, None, :]).sum(-1).max(axis=1))
    return best_e, best_f


def _bench_ensemble(n_total):
    """Fused E-panel ensemble cascade vs the single-embedder cascade
    (DESIGN.md §13): one pilot-routed kernel pass over E stacked key
    panels with the weighted fused score computed in-VMEM.

    Hard asserts carried by these rows:

      * fused recall >= the best single embedder's *exact* recall (the
        ensemble claim from arxiv 2507.07061 — exact per-panel recall
        is an upper bound on any single-embedder cascade, so this is
        the strong form);
      * the forced kernel is bit-exact with the E-panel jnp cascade
        (scores, ids, hit set — every EnsembleResult field);
      * int8 fused recall within 0.5% of fp32 fused;
      * fused p50 <= 1.6x the single-embedder fused p50 (vs ~E x for
        the sequential path) — asserted off-CPU, recorded as a
        structured skip on CPU where the panels' extra flops are not
        hidden behind the amortized bucket gather.
    """
    e = max(_ensemble_e(), 2)
    n_clusters, bucket, iters = SIZES.get(
        n_total, (max(n_total // 512, 16), 1024, 2))
    tag = f"tiered/ensemble/{n_total // 1024}k"
    rng = np.random.default_rng(SEED + 9)
    panels = _ens_corpus(rng, n_total, n_clusters, e)
    _, hot, warm = _states(panels[:, 0], n_clusters, bucket, iters)
    warm_n = n_total - HOT
    ens = tiers.make_ensemble(
        jnp.asarray(panels[warm_n:].transpose(1, 0, 2)),
        jnp.asarray(panels[:warm_n].transpose(1, 0, 2)))
    qp = _ens_queries(rng, panels)
    w = np.full((Q, e), 1.0 / e, np.float32)
    tenants = jnp.zeros((Q,), jnp.int32)
    thresholds = jnp.full((Q,), ENS_THRESHOLD, jnp.float32)
    pos = slice(0, Q // 2)

    best_e, best_f = _ens_exact(panels, qp, w)
    single_recalls = (best_e[pos] >= ENS_THRESHOLD).mean(axis=0)
    best_single = float(single_recalls.max())

    # the single-embedder production path on the pilot panel — the
    # latency denominator of the tentpole claim
    single_fn = jax.jit(partial(
        tiers.cascade_query, k=1, n_probe=N_PROBE, tail=0))
    qpilot = jnp.asarray(qp[:, 0])
    res_s = single_fn(hot, warm, qpilot, tenants, thresholds)
    jax.block_until_ready(res_s)
    p50_single, us_single = _timed_p50(
        lambda: jax.block_until_ready(
            single_fn(hot, warm, qpilot, tenants, thresholds)))
    yield f"{tag}/single_pilot", us_single / Q, {
        "n": n_total, "e": 1, "threshold": ENS_THRESHOLD,
        "us_per_query": us_single / Q, "p50_us": p50_single,
        "recall_at_thr": float(np.asarray(res_s.hit)[pos].mean())}

    qe, wj = jnp.asarray(qp), jnp.asarray(w)
    ens_kw = dict(k=1, n_probe=N_PROBE, tail=0)
    recalls = {}
    for name, kw in (("fused", {}), ("fused_int8", {"quantized": True})):
        fn = jax.jit(partial(tiers.ensemble_cascade_query, **ens_kw, **kw))
        res = fn(hot, warm, ens, qe, wj, tenants, thresholds)
        jax.block_until_ready(res)
        hit = np.asarray(res.hit)
        recall = recalls[name] = float(hit[pos].mean())
        false_hits = int(hit[Q // 2:].sum())
        p50, us = _timed_p50(
            lambda fn=fn: jax.block_until_ready(
                fn(hot, warm, ens, qe, wj, tenants, thresholds)))
        ratio = p50 / max(p50_single, 1e-9)
        yield f"{tag}/{name}", us / Q, {
            "n": n_total, "e": e, "threshold": ENS_THRESHOLD,
            "us_per_query": us / Q, "p50_us": p50,
            "recall_at_thr": recall, "false_hits": false_hits,
            "best_single_recall": round(best_single, 4),
            "p50_ratio_vs_single": round(ratio, 4),
            "speedup_vs_sequential": round(
                e * p50_single / max(p50, 1e-9), 4)}
        if name == "fused":
            assert recall >= best_single, \
                f"{tag}: fused recall {recall} below the best single " \
                f"embedder's exact recall {best_single}"
            assert false_hits <= 2, \
                f"{tag}: fused path leaks {false_hits} false hits on " \
                "novel queries"
            aname = f"{tag}/ensemble_speedup"
            if jax.default_backend() == "cpu":
                _assert_skipped(
                    aname, "cpu backend: the <=1.6x claim is a "
                    "bandwidth-amortization property of accelerator "
                    "dispatch; host threads pay the E-panel flops "
                    f"serially (measured ratio {ratio:.2f}x)")
            else:
                _assert_checked(aname)
                assert ratio <= ENS_MAX_P50_RATIO, \
                    f"{tag}: fused E={e} p50 {p50:.0f}us is " \
                    f"{ratio:.2f}x the single-embedder p50 " \
                    f"{p50_single:.0f}us (bound {ENS_MAX_P50_RATIO}x)"
        else:
            assert recall >= recalls["fused"] - 0.005, \
                f"{tag}: int8 fused recall {recall} dropped > 0.5% " \
                f"below fp32 {recalls['fused']}"

    # bit-exact parity: the fused kernel (forced; interpret mode
    # off-TPU) against the served E-panel jnp cascade in ref.py
    oracle = jax.jit(partial(tiers.ensemble_cascade_query, **ens_kw))(
        hot, warm, ens, qe, wj, tenants, thresholds)
    kernel = jax.jit(partial(tiers.ensemble_cascade_query,
                             use_kernel=True, **ens_kw))(
        hot, warm, ens, qe, wj, tenants, thresholds)
    for field in tiers.EnsembleResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(oracle, field)),
            np.asarray(getattr(kernel, field)),
            err_msg=f"{tag}: fused kernel diverges from the E-panel "
                    f"oracle on {field}")


def _ens_stream_panels(rng, z, e, info, tight=0.03, loose=0.85):
    """Panels of a latent batch (B, E, D): the informative embedder
    sees a tight paraphrase of the latent, the rest mostly noise — the
    regime where uniform weights drown the one good signal and the
    learned mixture recovers it."""
    return np.stack(
        [_unit(z + (tight if j == info else loose)
               * rng.standard_normal(z.shape).astype(np.float32))
         for j in range(e)], 1).astype(np.float32)


def _bench_ensemble_weights():
    """Uniform vs learned per-tenant mixture weights on a drifting
    stream (DESIGN.md §13).

    Both services serve the same E-embedder stream in which only one
    embedder separates duplicates from novel traffic; the stream
    starts novel-heavy (the non-duplicate labeled events) and drifts
    duplicate-heavy.  The uniform service averages the informative
    panel down below the operating threshold, re-admitting every
    near-duplicate; the learned service's ridge refit upweights the
    informative embedder from the (per-embedder score, duplicate)
    events and the duplicates start hitting.  Hard asserts: learned
    duplicate admissions strictly below uniform, learned probe recall
    strictly above uniform, the false-hit budget holds, and at least
    one weight refit actually applied with the informative embedder
    upweighted."""
    e = max(_ensemble_e(), 2)
    info = 1
    results = {}
    for mode in ("uniform", "learned"):
        learned = mode == "learned"
        rng = np.random.default_rng(SEED + 8)
        intents = _unit(rng.standard_normal((48, DIM)).astype(np.float32))
        svc = CacheService(
            dim=DIM, hot_capacity=256, warm_capacity=1024, n_clusters=16,
            bucket=128, n_probe=4, threshold=0.9, flush_size=64,
            kmeans_iters=2, seed=SEED, embedders=e,
            learned_admission=learned,
            feedback_config=FeedbackConfig(
                min_samples=48, min_class=8, refit_interval=32,
                max_step=0.03, seed=SEED) if learned else None)
        seen, dup_admits, admits, hits, lat = set(), 0, 0, 0, []
        for b in range(24):
            # drift: the first 3 batches cover every intent once
            # (novel traffic), the rest are duplicate-heavy revisits
            ids = (np.arange(b * 16, b * 16 + 16) % 48
                   if b < 3 else rng.integers(0, 48, 16))
            embs = _ens_stream_panels(rng, intents[ids], e, info)
            t0 = time.perf_counter()
            plan = svc.plan(CacheRequest.build(embs))
            svc.commit(plan, [f"ans{i}" for i in ids])
            svc.maintenance()
            lat.append(time.perf_counter() - t0)
            hits += int(plan.hit.sum())
            for row in plan.miss_rows():
                if not plan.admit[row]:
                    continue
                admits += 1
                if int(ids[row]) in seen:
                    dup_admits += 1
                seen.add(int(ids[row]))
        prng = np.random.default_rng(SEED + 18)
        probe_pos = _ens_stream_panels(prng, intents, e, info)
        probe_neg = _ens_stream_panels(
            prng, _unit(prng.standard_normal((64, DIM)).astype(np.float32)),
            e, info)
        pos_plan = svc.plan(CacheRequest.build(probe_pos), coalesce=False)
        neg_plan = svc.plan(CacheRequest.build(probe_neg), coalesce=False)
        st = svc.feedback.state() if svc.feedback is not None else {}
        wts = svc.policies.get_weights(0, e)
        results[mode] = {
            "queries": 24 * 16, "e": e, "hits": hits, "admitted": admits,
            "dup_admissions": dup_admits,
            "recall_probe": float(pos_plan.hit.mean()),
            "false_hits_probe": int(neg_plan.hit.sum()),
            "weight_refits": int(st.get("weight_refits_applied", 0)),
            "weights_final": [round(float(x), 3) for x in wts],
            "p50_us": float(np.percentile(np.asarray(lat) * 1e6, 50)),
        }
        yield f"tiered/ensemble/weights_{mode}", \
            results[mode]["p50_us"], results[mode]

    uni, lrn = results["uniform"], results["learned"]
    # the learned-mixture rows exist to back these claims
    assert lrn["dup_admissions"] < uni["dup_admissions"], \
        f"learned weights did not reduce duplicate admissions " \
        f"({lrn['dup_admissions']} vs {uni['dup_admissions']})"
    assert lrn["recall_probe"] > uni["recall_probe"], \
        f"learned weights did not lift probe recall " \
        f"({lrn['recall_probe']} vs {uni['recall_probe']})"
    assert lrn["false_hits_probe"] <= max(1, int(0.02 * 64)), \
        f"learned weights leak false hits ({lrn['false_hits_probe']}/64)"
    assert lrn["weight_refits"] >= 1, "no weight refit was ever applied"
    assert lrn["weights_final"][info] > 1.0 / e, \
        f"informative embedder not upweighted ({lrn['weights_final']})"


def _service_on(keys, n_clusters, bucket, iters, background):
    """A live CacheService grafted onto bulk-loaded tier states (this
    bench times serving, not fills)."""
    n_total = len(keys)
    _, hot, warm = _states(keys, n_clusters, bucket, iters)
    svc = CacheService(dim=DIM, hot_capacity=HOT,
                       warm_capacity=n_total - HOT, n_clusters=n_clusters,
                       bucket=bucket, n_probe=N_PROBE,
                       threshold=THRESHOLD, flush_size=512, rebuild_every=2,
                       kmeans_iters=iters, seed=SEED,
                       background_rebuild=background)
    svc.hot, svc.warm = hot, warm
    svc._next_vid = n_total
    return svc


def _stall_trace(svc, q, ticks=32, flush_at=8):
    """Per-tick serving latency; one tick also triggers the demotion
    flush whose IVF re-cluster either runs inline (stalling that tick)
    or double-buffered (shadow build + publish via maintenance())."""
    req = CacheRequest.build(np.asarray(q))
    svc.plan(req)                                    # warmup / compile
    # warm the flush-path jits on discarded states so the stall tick
    # measures the k-means itself, not tracing
    _, dem = svc._demote(svc.hot)
    w2, _ = svc._append(svc.warm, dem)
    jax.block_until_ready(svc._rebuild(w2))
    lat = []
    for t in range(ticks):
        t0 = time.perf_counter()
        if t == flush_at:
            svc.flush(rebuild=True)
        svc.maintenance()            # pipeline step: publish if finished
        svc.plan(req)
        lat.append(time.perf_counter() - t0)
    svc.maintenance(block=True)      # account the rebuild fully
    return np.asarray(lat)


def _bench_rebuild_stall(n_total, n_clusters, bucket, iters):
    """Inline vs background (double-buffered) rebuild: p50/p99 of the
    per-tick serving latency around one flush+re-cluster."""
    tag = f"tiered/{n_total // 1024}k"
    rng = np.random.default_rng(SEED + 1)
    keys = _corpus(rng, n_total, n_clusters)
    q = _queries(rng, keys)
    p50s, p99s, walls = {}, {}, {}
    for mode, background in (("inline", False), ("bg", True)):
        svc = _service_on(keys, n_clusters, bucket, iters, background)
        lat_us = _stall_trace(svc, q) * 1e6
        p50, p99 = np.percentile(lat_us, [50, 99])
        reb = svc.stats_snapshot().rebuild
        assert reb["rebuilds"] >= 1, (mode, reb)
        p50s[mode], p99s[mode] = p50, p99
        walls[mode] = float(reb["total_wall_s"])
        yield f"{tag}/serve_{mode}_rebuild", p50, {
            "p50_us": p50, "p99_us": p99,
            "rebuild_ms": float(reb["total_wall_s"]) * 1e3,
            "bg_rebuilds": reb["shadow_started"], "ticks": len(lat_us)}
    # the claim this bench exists for: once the rebuild dwarfs a
    # serving tick, double-buffering takes it off the serving p99.
    # Below that scale (e.g. 16k on 2 CPU cores, where the re-cluster
    # costs about one tick) the shadow thread's CPU contention can
    # outweigh the stall it removes — and p99-vs-p99 is timing-noisy
    # on contended runners — so a regression here warns loudly instead
    # of aborting the sweep (the recall/parity asserts stay hard).
    if walls["inline"] * 1e6 > 5 * p50s["inline"] \
            and p99s["bg"] >= p99s["inline"]:
        print(f"WARNING: {tag}: background rebuild did not lower the "
              f"serving p99 (inline {p99s['inline']:.0f}us vs bg "
              f"{p99s['bg']:.0f}us, rebuild {walls['inline']:.2f}s)",
              file=sys.stderr)


def _device_states(device_keys, vid0, hot_n, n_clusters, bucket, iters):
    """Bulk hot + warm states over ``device_keys`` whose value ids are
    the *global* corpus indices ``vid0..vid0+len`` — the device slice
    of a corpus whose remainder lives only in the cold tier."""
    n = len(device_keys)
    warm_n = n - hot_n
    vids = jnp.arange(vid0, vid0 + n, dtype=jnp.int32)
    warm = tiers.init_warm(warm_n, DIM, n_clusters, bucket)._replace(
        keys=jnp.asarray(device_keys[:warm_n]),
        valid=jnp.ones((warm_n,), bool),
        tenants=jnp.zeros((warm_n,), jnp.int32),
        value_ids=vids[:warm_n],
        write_seq=jnp.arange(1, warm_n + 1, dtype=jnp.int32),
        total=jnp.asarray(warm_n, jnp.int32))
    warm = jax.jit(partial(tiers.warm_rebuild, iters=iters, seed=SEED))(warm)
    warm = tiers.requantize(warm)
    hot = tiers.init_hot(hot_n, DIM)._replace(
        keys=jnp.asarray(device_keys[warm_n:]),
        valid=jnp.ones((hot_n,), bool),
        tenants=jnp.zeros((hot_n,), jnp.int32),
        last_used=jnp.arange(1, hot_n + 1, dtype=jnp.int32),
        value_ids=vids[warm_n:],
        clock=jnp.asarray(hot_n, jnp.int32))
    return hot, warm


def _cold_service(keys, hot_n, warm_n, n_clusters, bucket, iters,
                  cold_policy=None):
    """A live CacheService whose device tiers hold only the *last*
    ``hot_n + warm_n`` corpus rows; with ``cold_policy`` the remaining
    rows are bulk-loaded into the host-RAM cold tier (equal device
    memory either way — the cold rows never touch HBM)."""
    n = len(keys)
    warm_lo = n - hot_n - warm_n
    hot, warm = _device_states(keys[warm_lo:], warm_lo, hot_n,
                               n_clusters, bucket, iters)
    svc = CacheService(dim=DIM, hot_capacity=hot_n, warm_capacity=warm_n,
                       n_clusters=n_clusters, bucket=bucket,
                       n_probe=N_PROBE, threshold=THRESHOLD,
                       flush_size=256, kmeans_iters=iters, seed=SEED,
                       cold_capacity=warm_lo if cold_policy else 0,
                       cold_policy=cold_policy)
    svc.hot, svc.warm = hot, warm
    svc._next_vid = n
    if cold_policy is not None and warm_lo:
        svc.cold.bulk_load(keys[:warm_lo],
                           np.arange(warm_lo, dtype=np.int64),
                           np.zeros(warm_lo, np.int32))
    return svc, warm_lo


def _exact_hit_mask(keys, qn):
    """Exact max-sim >= THRESHOLD per query over the full corpus,
    chunked on the host (the corpus deliberately exceeds what the flat
    device store should be asked to hold)."""
    best = np.full(len(qn), -1.0, np.float32)
    for lo in range(0, len(keys), 1 << 18):
        best = np.maximum(best, (qn @ keys[lo:lo + (1 << 18)].T
                                 ).max(axis=1))
    return best >= THRESHOLD


def _cold_queries(rng, keys, warm_lo, exclude=None):
    """Half near-duplicates of cold-resident rows, a quarter of
    device-resident rows, a quarter novel — the mix that separates
    warm-only recall from cold-enabled recall."""
    pool = np.arange(warm_lo)
    if exclude is not None:
        pool = np.setdiff1d(pool, exclude)
    ci = rng.choice(pool, Q // 2, replace=False)
    di = warm_lo + rng.choice(len(keys) - warm_lo, Q // 4, replace=False)
    pos = keys[np.concatenate([ci, di])]
    pos = _unit(pos + 0.05 * rng.standard_normal(pos.shape
                                                 ).astype(np.float32))
    neg = _unit(rng.standard_normal((Q - len(pos), DIM)).astype(np.float32))
    return np.concatenate([pos, neg]).astype(np.float32), ci


def _bench_cold_tier(n_total):
    """Warm-only vs cold-enabled recall at equal device memory, cold
    hit-rate/fetch accounting, and one timed promotion drain
    (DESIGN.md §12).  The device slice is fixed at COLD_HOT + COLD_WARM
    rows regardless of ``n_total`` — past 64k the corpus mostly lives
    in host RAM, which is the whole point."""
    tag = f"tiered/cold/{n_total // 1024}k"
    n_groups = max(n_total // 64, 64)
    rng = np.random.default_rng(SEED + 5)
    keys = _corpus(rng, n_total, n_groups)
    cold_n = n_total - COLD_HOT - COLD_WARM
    assert cold_n > 0, f"cold bench needs > {COLD_HOT + COLD_WARM} rows"
    # the router gate self-calibrates to the corpus's cluster spread
    # at route-fit time (cold.rebuild_routes); only the shape knobs
    # scale with the corpus here
    pol = ColdRoutingPolicy(
        n_probe=8, fetch_budget=64, promote_max=512,
        n_clusters=min(256, max(64, cold_n // 4096)),
        kmeans_iters=4, kmeans_sample=1 << 16,
        route_rebuild_every=1 << 30, seed=SEED)
    q, cold_idx = _cold_queries(rng, keys, cold_n)
    exact_hit = _exact_hit_mask(keys, q)
    req = CacheRequest.build(q)

    recalls = {}
    for mode, policy in (("warm_only", None), ("cold_enabled", pol)):
        svc, warm_lo = _cold_service(keys, COLD_HOT, COLD_WARM,
                                     *SIZES[COLD_WARM], cold_policy=policy)
        plan = svc.plan(req, coalesce=False)
        recall, spurious = _recall(plan, exact_hit)
        recalls[mode] = recall
        p50, us = _timed_p50(lambda: svc.plan(req, coalesce=False),
                             repeats=5)
        derived = {
            "n": n_total, "device_rows": COLD_HOT + COLD_WARM,
            "cold_rows": warm_lo if policy else 0,
            "us_per_query": us / Q, "p50_us": p50,
            "recall_at_thr": recall, "spurious_hits": spurious,
            "hits": int(plan.hit.sum()),
            # under an ensemble service the cold tier is consulted on
            # the pilot panel only (DESIGN.md §13)
            "ensemble": "pilot"}
        if policy is not None:
            st = svc.stats_snapshot().tiers["cold"]
            consulted = max(st["cold_fetches"], 1)
            derived.update({
                "cold_hits": st["cold_hits"],
                "cold_hit_rate": round(st["cold_hits"] / consulted, 4),
                "cold_fetches": st["cold_fetches"],
                "cold_fetched_rows": st["cold_fetched_rows"],
                "cold_router_skips": st["cold_router_skips"],
                "cold_route_slack": st["cold_route_slack"]})
        yield f"{tag}/{mode}", us / Q, derived

        if policy is None:
            continue
        # the row this subsystem exists for: at byte-identical device
        # tiers, the cold fallback must strictly lift recall
        assert recalls["cold_enabled"] > recalls["warm_only"], \
            f"{tag}: cold tier did not lift recall " \
            f"({recalls['cold_enabled']} vs {recalls['warm_only']} " \
            f"warm-only at equal device memory)"
        assert st["cold_hits"] > 0, f"{tag}: no cold hits recorded"

        # promotion drain: warm up the append path on the first batch
        # of queued re-hot rows, then time a fresh drain end to end
        svc.maintenance()
        q2, _ = _cold_queries(np.random.default_rng(SEED + 6), keys,
                              cold_n, exclude=cold_idx)
        svc.plan(CacheRequest.build(q2), coalesce=False)
        pending = svc.cold.pending_promotions
        t0 = time.perf_counter()
        rep = svc.maintenance()
        wall_us = (time.perf_counter() - t0) * 1e6
        assert rep.cold_promoted > 0, f"{tag}: promotion drain was empty"
        assert svc.cold.pending_promotions == 0
        yield f"{tag}/promotion", wall_us, {
            "promoted": rep.cold_promoted, "pending_before": pending,
            "wall_us": wall_us,
            "us_per_row": wall_us / rep.cold_promoted}


def _bench_cold_overhead():
    """p50 ratio of the served path with the cold tier enabled vs
    disabled at a warm-only-feasible size: every query is answerable
    on-device, so the cold path's only job is to get out of the way —
    the tight default router margin declines the novel-query fetches.
    The ratio is bounded here and tracked by the trajectory gate."""
    n, hot_n = 1 << 13, COLD_HOT
    n_clusters, bucket, iters = 64, 256, 2
    rng = np.random.default_rng(SEED + 7)
    keys = _corpus(rng, n, n // 64)
    q = np.asarray(_queries(rng, keys))
    req = CacheRequest.build(q)

    p50s = {}
    for mode, policy in (("off", None),
                         ("on", ColdRoutingPolicy(seed=SEED))):
        hot, warm = _device_states(keys, 0, hot_n, n_clusters, bucket,
                                   iters)
        svc = CacheService(dim=DIM, hot_capacity=hot_n,
                           warm_capacity=n - hot_n,
                           n_clusters=n_clusters, bucket=bucket,
                           n_probe=N_PROBE, threshold=THRESHOLD,
                           flush_size=256, kmeans_iters=iters, seed=SEED,
                           cold_capacity=n if policy else 0,
                           cold_policy=policy)
        svc.hot, svc.warm = hot, warm
        svc._next_vid = n
        if policy is not None:
            # a full copy of the corpus in cold — the worst case for
            # router work on every below-threshold query
            svc.cold.bulk_load(keys, np.arange(n, dtype=np.int64),
                               np.zeros(n, np.int32))
        p50s[mode], _ = _timed_p50(
            lambda: svc.plan(req, coalesce=False), repeats=15)
    ratio = p50s["on"] / max(p50s["off"], 1e-9)
    # generous hard bound — the trajectory gate holds the tight one
    # (CPU runners are contended; a genuine regression blows past 2.5x)
    assert ratio < 2.5, \
        f"cold tier inflates warm-feasible serving p50 {ratio:.2f}x " \
        f"({p50s['on']:.0f}us vs {p50s['off']:.0f}us)"
    yield "tiered/cold/p50_ratio", p50s["on"], {
        "n": n, "p50_on_us": p50s["on"], "p50_off_us": p50s["off"],
        "p50_ratio": round(ratio, 4)}


def _drift_stream(rng, intents, n_batches=24, batch=32):
    """A paraphrase stream whose duplicate pressure drifts mid-run: the
    first third is mostly novel traffic with tight paraphrases, the
    rest is duplicate-heavy with noisier paraphrases that land *below*
    the static threshold — the regime where a frozen admission rule
    fills the store with near-duplicates."""
    for b in range(n_batches):
        drift = b >= n_batches // 3
        noise = 0.06 if drift else 0.02
        ids = rng.integers(0, len(intents), batch)
        embs = _unit(intents[ids] + noise * rng.standard_normal(
            (batch, DIM)).astype(np.float32))
        yield embs, ids


def _bench_admission_drift():
    """Learned vs fixed admission on the drifting stream (DESIGN.md §9).

    Both services start from the same static operating point
    (threshold 0.95, margin 0.02); the learned one labels every commit
    against its stored neighbour and lets ``maintenance()`` refit the
    tenant's threshold/margin from the observed duplicate rate.  The
    claim the rows carry: duplicate admissions drop, end recall on
    fresh paraphrases holds, and novel probes stay below the false-hit
    budget — asserted hard, not just reported.
    """
    rng = np.random.default_rng(SEED + 2)
    n_intents = 64
    intents = _unit(rng.standard_normal((n_intents, DIM)
                                        ).astype(np.float32))
    stream = list(_drift_stream(rng, intents))
    n_queries = sum(len(ids) for _, ids in stream)
    # probes: fresh tight paraphrases (recall) + novel queries (budget)
    probe_pos = _unit(intents + 0.03 * rng.standard_normal(
        intents.shape).astype(np.float32))
    probe_neg = _unit(rng.standard_normal((64, DIM)).astype(np.float32))

    results = {}
    for mode in ("fixed", "learned"):
        learned = mode == "learned"
        svc = CacheService(
            dim=DIM, hot_capacity=256, warm_capacity=1024, n_clusters=16,
            bucket=128, n_probe=4, threshold=0.95, admission_margin=0.02,
            flush_size=64, kmeans_iters=2, seed=SEED,
            learned_admission=learned,
            feedback_config=FeedbackConfig(
                min_samples=48, refit_interval=32, max_step=0.03,
                seed=SEED) if learned else None)
        seen, dup_admits, admits, hits, lat = set(), 0, 0, 0, []
        for embs, ids in stream:
            t0 = time.perf_counter()
            plan = svc.plan(CacheRequest.build(embs))
            svc.commit(plan, [f"ans{i}" for i in ids])
            svc.maintenance()
            lat.append(time.perf_counter() - t0)
            hits += int(plan.hit.sum())
            for row in plan.miss_rows():
                if not plan.admit[row]:
                    continue
                admits += 1
                if int(ids[row]) in seen:
                    dup_admits += 1   # a same-intent entry already lives
                seen.add(int(ids[row]))
        pos_plan = svc.plan(CacheRequest.build(probe_pos), coalesce=False)
        neg_plan = svc.plan(CacheRequest.build(probe_neg), coalesce=False)
        learning = svc.stats_snapshot().learning or {}
        pol = svc.policies.get(0)
        results[mode] = {
            "queries": n_queries, "hits": hits, "admitted": admits,
            "dup_admissions": dup_admits,
            "dup_admit_rate": dup_admits / max(admits, 1),
            "recall_probe": float(pos_plan.hit.mean()),
            "false_hits_probe": int(neg_plan.hit.sum()),
            "threshold_final": round(float(pol.threshold), 4),
            "margin_final": round(float(pol.admission_margin), 4),
            "refits": int(learning.get("refits_applied", 0)),
            "p50_us": float(np.percentile(np.asarray(lat) * 1e6, 50)),
        }
        yield f"tiered/admission_{mode}", results[mode]["p50_us"], \
            results[mode]

    fixed, learned = results["fixed"], results["learned"]
    # the learned rows exist to back these three claims
    assert learned["dup_admissions"] < fixed["dup_admissions"], \
        f"learned admission did not reduce duplicate admissions " \
        f"({learned['dup_admissions']} vs {fixed['dup_admissions']})"
    assert learned["recall_probe"] >= fixed["recall_probe"] - 0.02, \
        f"learned admission regressed probe recall " \
        f"({learned['recall_probe']} vs {fixed['recall_probe']})"
    assert learned["false_hits_probe"] <= max(
        1, int(0.02 * len(probe_neg))), \
        f"learned threshold leaks false hits " \
        f"({learned['false_hits_probe']}/{len(probe_neg)} novel probes)"
    assert learned["refits"] >= 1, "no refit was ever applied"


def _topic_stream(rng, n_batches, batch, pool, seen, repeat):
    """Batches of rendered medical queries over a pool of
    (entity, aspect) topics: each query is either a paraphrase of an
    already-seen topic (probability ``repeat`` — a cacheable repeat)
    or a novel topic drawn from ``pool``.  ``seen`` accumulates across
    calls so a later phase keeps revisiting earlier topics."""
    out = []
    for _ in range(n_batches):
        qs = []
        for _ in range(batch):
            if seen and rng.random() < repeat:
                ent, asp = seen[int(rng.integers(len(seen)))]
            else:
                ent, asp = pool[int(rng.integers(len(pool)))]
                if (ent, asp) not in seen:
                    seen.append((ent, asp))
            qs.append(render_query(rng, "medical", ent, asp))
        out.append(qs)
    return out


def _bench_embedder_refresh():
    """Frozen vs online-refreshed embedder on a drifting-topic stream
    (DESIGN.md §11).

    Both services share one general-purpose base embedder (the compact
    encoder pre-trained on out-of-domain quora pairs — the paper's
    general-purpose starting point) and the same serving threshold.
    The stream serves medical-domain traffic in two phases: phase A
    over one topic slice feeds the pair reservoir, then the refreshed
    service runs one ``maintenance()`` refresh cycle — contrastive
    fine-tune on pooled+synthetic pairs, eval gate, shadow re-embed,
    versioned publish — before phase B drifts onto unseen topics.
    Only phase B is measured.

    Hits are scored against intent ground truth (the committed
    response encodes the query's entity+aspect): a hit that serves the
    right intent is a true positive, the wrong intent a false
    positive, and a miss on an already-stored intent a false negative.
    The refresh policy recalibrates at publish — the candidate scores
    pairs on its own scale, so the swap also remaps the serving
    threshold to the candidate's held-out operating point instead of
    reusing the frozen scalar (``recalibrate=True``, DESIGN.md §11).
    The rows carry the paper's core claim as hard asserts: the
    domain-adapted embedder beats the general-purpose one on *both*
    hit precision and hit recall, the publish actually happened
    (``embed_version >= 1``), and every committed entry still hits
    after the hot swap (``overlap_recall == 1.0`` — the re-embed
    rewrote every stored key under the new encoder).
    """
    enc = get_config("modernbert-149m").reduced(vocab_size=2048)
    tok = HashTokenizer(vocab_size=enc.vocab_size)
    base_ft = FinetuneConfig(epochs=4, batch_size=32, max_len=24,
                             lr=5e-4, margin=0.7)
    base = EmbedderTrainer(enc, base_ft)
    base.fit(make_pair_dataset("quora", 1024, seed=1), tok)
    # the serving trainer's ft drives the refresh fit (§11): a longer
    # schedule than the base, since the candidate must overcome the
    # quora prior from a few hundred pooled+synthetic pairs
    serve_ft = FinetuneConfig(epochs=8, batch_size=32, max_len=24,
                              lr=5e-4, margin=0.7)

    entities, aspects = DOMAINS["medical"]
    topics = [(entities[i], aspects[i % len(aspects)])
              for i in range(36)]
    threshold = 0.9

    results = {}
    for mode in ("frozen", "refreshed"):
        refreshed = mode == "refreshed"
        # identical stream per mode: same rng -> same queries
        rng = np.random.default_rng(SEED + 4)
        seen = []
        phase_a = _topic_stream(rng, 10, 16, topics[:12], seen, 0.5)
        phase_b = _topic_stream(rng, 24, 16, topics[12:], seen, 0.6)
        trainer = EmbedderTrainer(enc, serve_ft, params=base.params)
        embed = trainer.make_embed_fn(tok)
        pol = EmbedderRefreshPolicy(
            min_pairs=32, min_class=4, refresh_interval=64,
            synth_domain="medical", synth_min_pairs=768,
            min_precision=0.6, min_recall=0.6, max_f1_regression=1.0,
            recalibrate=True)
        svc = CacheService(
            dim=enc.d_model, hot_capacity=512, warm_capacity=1024,
            n_clusters=16, bucket=128, n_probe=4, threshold=threshold,
            admission_margin=0.02, seed=SEED,
            embedder_trainer=trainer if refreshed else None,
            embedder_tokenizer=tok if refreshed else None,
            refresh_policy=pol if refreshed else None)

        stored, committed = set(), {}
        cnt = {"tp": 0, "fp": 0, "fn": 0}
        lat = []

        def serve(batches, measure):
            for qs in batches:
                texts = [q.text for q in qs]
                t0 = time.perf_counter()
                plan = svc.plan(CacheRequest.build(
                    embed(texts), 0, texts=texts), coalesce=False)
                svc.commit(plan, [
                    None if h else f"ans:{q.entity}|{q.aspect}"
                    for h, q in zip(plan.hit, qs)])
                svc.maintenance()
                if measure:
                    lat.append(time.perf_counter() - t0)
                for row, q in enumerate(qs):
                    truth = f"ans:{q.entity}|{q.aspect}"
                    if measure:
                        if plan.hit[row]:
                            right = plan.responses[row] == truth
                            cnt["tp" if right else "fp"] += 1
                        elif (q.entity, q.aspect) in stored:
                            cnt["fn"] += 1
                    if plan.admit[row] and not plan.hit[row]:
                        stored.add((q.entity, q.aspect))
                        committed[q.text] = truth

        serve(phase_a, measure=False)
        version, refresh_wall = 0, 0.0
        if refreshed:
            svc.maintenance()                 # trips the refresh start
            rep = svc.maintenance(block=True)  # join + publish
            version = rep.embed_version
            refresh_wall = rep.refresh_wall_s
        serve(phase_b, measure=True)
        svc.maintenance(block=True)           # join any trailing cycle

        # overlap recall: every committed entry must still hit through
        # (and after) the hot swap — the shadow re-embed rewrote the
        # stored keys under whichever encoder is now live
        probe = sorted(committed)
        probe_plan = svc.plan(CacheRequest.build(
            embed(probe), 0, texts=probe), coalesce=False)
        tp, fp, fn = cnt["tp"], cnt["fp"], cnt["fn"]
        results[mode] = {
            "queries": 24 * 16, "tp": tp, "fp": fp, "fn": fn,
            # the refresh cycle is mutually exclusive with ensemble
            # serving (a panel publish is the A/B analogue, §13)
            "ensemble": "off",
            "hit_precision": round(tp / max(tp + fp, 1), 4),
            "hit_recall": round(tp / max(tp + fn, 1), 4),
            "overlap_recall": float(probe_plan.hit.mean()),
            "entries": len(probe),
            "embed_version": int(version),
            "threshold_final": round(
                float(svc.policies.get(0).threshold), 4),
            "refresh_wall_s": round(float(refresh_wall), 3),
            "p50_us": float(np.percentile(np.asarray(lat) * 1e6, 50)),
        }
        yield f"tiered/embedder_{mode}", results[mode]["p50_us"], \
            results[mode]

    frozen, refr = results["frozen"], results["refreshed"]
    # the §11 rows exist to back these claims
    assert refr["embed_version"] >= 1, \
        "the refresh cycle never published a new embedder version"
    for mode, row in results.items():
        assert row["overlap_recall"] == 1.0, \
            f"{mode}: committed entries lost through the hot swap " \
            f"(overlap recall {row['overlap_recall']})"
    assert refr["hit_precision"] > frozen["hit_precision"], \
        f"refreshed embedder did not improve hit precision " \
        f"({refr['hit_precision']} vs {frozen['hit_precision']})"
    assert refr["hit_recall"] > frozen["hit_recall"], \
        f"refreshed embedder did not improve hit recall " \
        f"({refr['hit_recall']} vs {frozen['hit_recall']})"


def _bench_telemetry():
    """Per-stage latency rows from the §10 registry plus the overhead
    guard: the same serving tick with the registry/tracer live must
    cost < 2% extra p50 vs ``Telemetry.disabled()`` (the registry's
    series handles are resolved once at construction; the hot path is
    an int/bisect update, DESIGN.md §10.1).  Two otherwise-identical
    services process the same batches tick-interleaved — alternating
    order per tick — so host noise lands on both sides of the pooled
    medians; the budget is asserted here and re-checked from the
    committed JSON by scripts/check_bench_trajectory.py."""
    tag = "tiered/serve"
    rng = np.random.default_rng(SEED + 3)
    intents = _unit(rng.standard_normal((32, DIM)).astype(np.float32))

    tel_on = Telemetry()
    svcs = {
        mode: CacheService(dim=DIM, hot_capacity=512, warm_capacity=1024,
                           n_clusters=16, bucket=128, n_probe=N_PROBE,
                           threshold=THRESHOLD, kmeans_iters=2, seed=SEED,
                           telemetry=tel)
        for mode, tel in (("on", tel_on), ("off", Telemetry.disabled()))}
    # identical warmup through both: pays the jit tracing up front and
    # seeds the store so the timed ticks are hit-heavy and unimodal
    # (32 intents never cross the flush watermark -> no rebuild ticks)
    warm = _unit(intents + 0.04 * rng.standard_normal(
        intents.shape).astype(np.float32))
    for svc in svcs.values():
        plan = svc.plan(CacheRequest.build(warm))
        svc.commit(plan, [f"warm{i}" for i in range(len(warm))])
        svc.maintenance()

    lat = {"on": [], "off": []}
    gc.collect()
    gc.disable()      # collection pauses land on whichever side is
    try:              # mid-tick; keep them out of the comparison
        for b in range(96):
            ids = rng.integers(0, len(intents), 32)
            embs = _unit(intents[ids] + 0.04 * rng.standard_normal(
                (32, DIM)).astype(np.float32))
            answers = [f"ans{i}" for i in ids]
            for mode in ("on", "off") if b % 2 == 0 else ("off", "on"):
                svc = svcs[mode]
                t0 = time.perf_counter()
                plan = svc.plan(CacheRequest.build(embs))
                svc.commit(plan, answers)
                svc.maintenance()
                lat[mode].append(time.perf_counter() - t0)
    finally:
        gc.enable()
    svcs["on"].maintenance(block=True)   # idle tick: drain SLO gauges

    stage_h = tel_on.stage_histogram()
    for stage in ("plan", "commit", "maintenance"):
        agg = stage_h.aggregate(stage=stage)
        assert agg.count, f"{tag}: stage {stage!r} was never observed"
        p50_us = agg.quantile(0.5) * 1e6
        yield f"{tag}/stage_{stage}", p50_us, {
            "p50_us": p50_us, "mean_us": agg.mean * 1e6,
            "count": int(agg.count)}

    # the on/off ticks are paired (same batch, adjacent in time), so
    # per-tick *differences* cancel the +-hundreds-of-us host jitter
    # a contended CPU runner puts on raw medians.  Jitter that still
    # leaks through a block's median only inflates it, never deflates
    # every block — so the min over block medians is the stable
    # overhead estimate, and a real regression (which lifts every
    # block) cannot hide under it.
    on_s, off_s = np.asarray(lat["on"]), np.asarray(lat["off"])
    p50_on = float(np.percentile(on_s * 1e6, 50))
    p50_off = float(np.percentile(off_s * 1e6, 50))
    d = (on_s - off_s).reshape(8, -1) * 1e6
    extra_us = float(np.median(d, axis=1).min())
    problems = check_overhead_budget(
        (p50_off + max(extra_us, 0.0)) / 1e6, p50_off / 1e6)
    assert not problems, f"{tag}: " + "; ".join(problems)
    yield f"{tag}/telemetry_overhead", p50_on, {
        "p50_on_us": p50_on, "p50_off_us": p50_off,
        "median_extra_us": extra_us,
        "overhead_ratio": round(
            (p50_off + max(extra_us, 0.0)) / max(p50_off, 1e-9), 4)}


def _json_path():
    env = os.environ.get("BENCH_CASCADE_JSON")
    if env is not None:
        return pathlib.Path(env) if env else None
    return pathlib.Path(__file__).resolve().parent.parent \
        / "results" / "BENCH_cascade.json"


def bench_tiered_cache():
    """Yields (name, us_per_call, derived_str) rows and, on completion,
    writes the raw rows to BENCH_cascade.json for the perf trajectory."""
    rows = []
    _ASSERTS["checked"], _ASSERTS["skipped"] = [], []
    for n_total in _sizes():
        for name, us, derived in _bench_one_size(n_total):
            rows.append({"name": name, "us_per_call": us, **derived})
            yield name, us, fmt_derived(derived)
    # fused multi-embedder ensemble: E-panel kernel pass + learned
    # mixture weights (DESIGN.md §13)
    for n_total in _ensemble_sizes():
        for name, us, derived in _bench_ensemble(n_total):
            rows.append({"name": name, "us_per_call": us, **derived})
            yield name, us, fmt_derived(derived)
    for name, us, derived in _bench_ensemble_weights():
        rows.append({"name": name, "us_per_call": us, **derived})
        yield name, us, fmt_derived(derived)
    # host-RAM cold tier: recall past device memory + overhead guard
    for n_total in _cold_sizes():
        for name, us, derived in _bench_cold_tier(n_total):
            rows.append({"name": name, "us_per_call": us, **derived})
            yield name, us, fmt_derived(derived)
    for name, us, derived in _bench_cold_overhead():
        rows.append({"name": name, "us_per_call": us, **derived})
        yield name, us, fmt_derived(derived)
    # size-independent: learned-vs-fixed admission on a drifting stream
    for name, us, derived in _bench_admission_drift():
        rows.append({"name": name, "us_per_call": us, **derived})
        yield name, us, fmt_derived(derived)
    # size-independent: frozen-vs-refreshed embedder on a topic drift
    for name, us, derived in _bench_embedder_refresh():
        rows.append({"name": name, "us_per_call": us, **derived})
        yield name, us, fmt_derived(derived)
    # size-independent: §10 stage breakdown + telemetry overhead guard
    for name, us, derived in _bench_telemetry():
        rows.append({"name": name, "us_per_call": us, **derived})
        yield name, us, fmt_derived(derived)
    path = _json_path()
    if path is not None:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({
            "bench": "tiered_cascade",
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "sizes": _sizes(),
            "cold_sizes": _cold_sizes(),
            "ensemble_sizes": _ensemble_sizes(),
            "ensemble_e": _ensemble_e(),
            "q": Q, "dim": DIM, "threshold": THRESHOLD,
            "checked_asserts": list(_ASSERTS["checked"]),
            "skipped_asserts": list(_ASSERTS["skipped"]),
            "rows": rows,
        }, indent=1) + "\n")
        print(f"# wrote {len(rows)} rows to {path}", file=sys.stderr)


def main() -> None:
    """Standalone entry with a CI-sized tier:
    ``python -m benchmarks.bench_tiered_cache --smoke`` runs the full
    row set (cascade paths, parity asserts, sharded + int8 rows,
    flush+rebuild, rebuild stall) on a 4k corpus in well under a
    minute."""
    import argparse

    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-corpus run (4k entries, 64k cold tier) "
                         "for CI")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_TIERED_SIZES"] = str(1 << 12)
        os.environ.setdefault("BENCH_COLD_SIZES", str(1 << 16))
        os.environ.setdefault("BENCH_ENSEMBLE_SIZES", str(1 << 14))
        os.environ.setdefault("BENCH_ENSEMBLE_E", "2")
    print("name,us_per_call,derived")
    for name, us, derived in bench_tiered_cache():
        print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()
