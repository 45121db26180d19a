"""Tiered multi-tenant CacheService: cascade recall vs exact,
tenant isolation, admission, response GC, and the serving wiring."""
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import commit_insert, plan_lookup

from repro.cache_service import CacheService, tiers
from repro.core.calibration import calibrate_for_false_hit_budget
from repro.core.embedders import HashNgramEmbedder
from repro.core.store import init_store, insert_batch, query
from repro.data import HashTokenizer, make_query_stream
from repro.serving import CachedLLMService

rng = np.random.default_rng(13)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _clustered(n_clusters=16, per=32, d=32, spread=0.12):
    cents = _unit(rng.standard_normal((n_clusters, d)).astype(np.float32))
    keys = np.repeat(cents, per, axis=0)
    return _unit(keys + spread * rng.standard_normal(keys.shape
                                                     ).astype(np.float32))


# ---------------------------------------------------------------------------
# tiered lookup equivalence vs flat brute force
# ---------------------------------------------------------------------------

def test_cascade_recall_matches_flat_exact():
    """Fill the service far past the hot capacity (most entries demoted
    into the warm IVF ring) and check the cascade reproduces the exact
    brute-force hit set on a clustered corpus."""
    keys = _clustered(n_clusters=16, per=32, d=32)
    N = len(keys)
    thr = 0.9
    svc = CacheService(dim=32, hot_capacity=64, warm_capacity=1024,
                       n_clusters=16, bucket=128, n_probe=6, threshold=thr,
                       flush_size=32, rebuild_every=2, kmeans_iters=6)
    for i in range(0, N, 32):
        commit_insert(svc, keys[i:i + 32],
                      [f"r{j}" for j in range(i, i + 32)])
    # most entries live in warm
    assert svc.stats_snapshot().tiers["demotions"] > N // 2

    q = _unit(keys + 0.02 * rng.standard_normal(keys.shape
                                                ).astype(np.float32))
    q_neg = _unit(rng.standard_normal((64, 32)).astype(np.float32))
    queries = np.concatenate([q, q_neg])

    flat = init_store(N, 32)
    flat = insert_batch(flat, jnp.asarray(keys), jnp.arange(N))
    exact = query(flat, jnp.asarray(queries), threshold=thr, k=1)
    exact_hit = np.asarray(exact.hit)

    hit, scores, values = plan_lookup(svc, queries)
    recall = (hit & exact_hit).sum() / max(exact_hit.sum(), 1)
    assert recall >= 0.95, recall
    # no spurious hits the exact store would miss
    assert not (hit & ~exact_hit).any()
    # every served value is live (never a GC'd placeholder)
    assert all(v is not None for v, h in zip(values, hit) if h)


def test_cascade_is_one_jitted_call_and_mixed_batches_dont_retrace():
    svc = CacheService(dim=16, hot_capacity=32, warm_capacity=128,
                       n_clusters=4, bucket=32)
    e = _unit(rng.standard_normal((8, 16)).astype(np.float32))
    commit_insert(svc, e, [f"r{i}" for i in range(8)], tenant=0)
    plan_lookup(svc, e, tenant=0)
    sizes = svc._lookup._cache_size()
    plan_lookup(svc, e, tenant=np.arange(8) % 3)   # mixed-tenant batch
    svc.set_tenant_policy(2, threshold=0.5)
    plan_lookup(svc, e, tenant=2)           # new per-tenant threshold
    assert svc._lookup._cache_size() == sizes   # same trace: no recompile


# ---------------------------------------------------------------------------
# tenant isolation
# ---------------------------------------------------------------------------

def test_cross_tenant_queries_never_hit():
    """Property-style sweep: identical keys inserted under tenant A must
    be invisible to tenant B, through demotion and ring wrap."""
    d = 24
    svc = CacheService(dim=d, hot_capacity=16, warm_capacity=64,
                       n_clusters=4, bucket=32, n_probe=4, threshold=0.8,
                       flush_size=8, rebuild_every=1)
    owner = {}
    for step in range(12):
        t = step % 3
        e = _unit(rng.standard_normal((8, d)).astype(np.float32))
        commit_insert(svc, e, [f"t{t}-{step}-{i}" for i in range(8)],
                      tenant=t)
        for row in e:
            owner[row.tobytes()] = t
        # every tenant queries every key ever inserted
        all_keys = np.asarray([np.frombuffer(b, np.float32)
                               for b in owner])
        for qt in range(3):
            hit, scores, values = plan_lookup(svc, all_keys, tenant=qt)
            for j, b in enumerate(owner):
                if owner[b] != qt:
                    assert not hit[j], (step, qt, j)
                if hit[j]:
                    assert values[j].startswith(f"t{qt}-")


def test_evict_tenant_between_plan_and_commit():
    """The plan/commit race: a tenant eviction landing between the two
    calls must neither resurrect freed value ids nor leak host response
    strings; hit responses stay valid (resolved at plan time)."""
    from repro.cache_service import CacheRequest

    d = 16
    svc = CacheService(dim=d, hot_capacity=32, warm_capacity=64,
                       n_clusters=4, bucket=32, threshold=0.9)
    e0 = _unit(rng.standard_normal((8, d)).astype(np.float32))
    commit_insert(svc, e0, [f"old{i}" for i in range(8)], tenant=0)

    fresh = _unit(rng.standard_normal((4, d)).astype(np.float32))
    q = np.concatenate([e0[:4], fresh])
    plan = svc.plan(CacheRequest.build(q, 0))
    assert plan.hit[:4].all() and not plan.hit[4:].any()
    assert all(r is not None for r in plan.responses[:4])

    assert svc.evict_tenant(0) == 8          # the race: plan is now stale
    receipt = svc.commit(plan, [None] * 4 + [f"new{i}" for i in range(4)])
    assert receipt.admitted == 4
    assert svc.stats_snapshot().traffic["stale_commits"] == 1
    # value ids 0..7 were freed; commit must have minted fresh ones only
    assert svc.responses and min(svc.responses) >= 8
    assert sorted(svc.responses.values()) == [f"new{i}" for i in range(4)]
    assert len(svc.responses) == len(svc)    # no leaked host strings
    # plan-time responses were already resolved, so the requests that
    # were promised a hit still got a real string (asserted above); but
    # the evicted keys themselves are gone from the device tiers
    hit, _, _ = plan_lookup(svc, e0, tenant=0)
    assert not hit.any()


def test_evict_tenant_only_touches_that_tenant():
    d = 16
    svc = CacheService(dim=d, hot_capacity=32, warm_capacity=64,
                       n_clusters=4, bucket=32, threshold=0.9)
    e0 = _unit(rng.standard_normal((4, d)).astype(np.float32))
    e1 = _unit(rng.standard_normal((4, d)).astype(np.float32))
    commit_insert(svc, e0, ["a"] * 4, tenant=0)
    commit_insert(svc, e1, ["b"] * 4, tenant=1)
    assert svc.evict_tenant(0) == 4
    assert not plan_lookup(svc, e0, tenant=0)[0].any()
    assert plan_lookup(svc, e1, tenant=1)[0].all()
    assert len(svc.responses) == 4


# ---------------------------------------------------------------------------
# admission + response GC
# ---------------------------------------------------------------------------

def test_admission_skips_well_covered_misses():
    d = 16
    svc = CacheService(dim=d, hot_capacity=32, warm_capacity=64,
                       n_clusters=4, bucket=32, threshold=0.95,
                       admission_margin=0.2)
    base = _unit(rng.standard_normal((1, d)).astype(np.float32))
    commit_insert(svc, base, ["orig"])
    orth = rng.standard_normal((1, d)).astype(np.float32)
    orth = _unit(orth - (orth @ base.T) * base)
    near = 0.85 * base + np.sqrt(1 - 0.85 ** 2) * orth  # cos(base,near)=.85
    hit, scores, _ = plan_lookup(svc, near)
    assert not hit[0] and scores[0] > 0.75  # miss, but well-covered
    admitted = commit_insert(svc, near, ["dup"], scores=scores)
    assert admitted == 0
    assert svc.stats_snapshot().admission["skipped"] == 1
    assert len(svc.responses) == 1          # no string leaked for the skip
    far = _unit(rng.standard_normal((1, d)).astype(np.float32))
    hit, scores, _ = plan_lookup(svc, far)
    assert commit_insert(svc, far, ["new"], scores=scores) == 1


def test_response_gc_bounds_host_memory():
    """Sustained traffic overwrites both tiers; the response dict must
    track live entries, not total inserts (the SemanticCache leak)."""
    d = 16
    hot_cap, warm_cap = 16, 32
    svc = CacheService(dim=d, hot_capacity=hot_cap, warm_capacity=warm_cap,
                       n_clusters=4, bucket=16, flush_size=8,
                       rebuild_every=1)
    total = 0
    for step in range(40):
        e = _unit(rng.standard_normal((8, d)).astype(np.float32))
        total += commit_insert(svc, e, [f"s{step}-{i}" for i in range(8)])
    assert total == 320
    assert len(svc.responses) <= hot_cap + warm_cap
    assert len(svc.responses) == len(svc)   # exactly the live entries
    assert svc.stats_snapshot().tiers["evictions"] == total - len(svc)


def test_manual_flushes_never_strand_entries_past_tail():
    """flush(rebuild=False) must not leave demoted rows beyond the tail
    window unreachable: the service forces a rebuild before the
    unindexed backlog outgrows the window."""
    d = 16
    svc = CacheService(dim=d, hot_capacity=32, warm_capacity=64,
                       n_clusters=4, bucket=32, threshold=0.9,
                       flush_size=8, rebuild_every=2)
    e = _unit(rng.standard_normal((32, d)).astype(np.float32))
    commit_insert(svc, e, [f"r{i}" for i in range(32)])
    for _ in range(4):
        svc.flush(rebuild=False)
    hit, _, _ = plan_lookup(svc, e)
    assert hit.all(), int(hit.sum())
    assert len(svc.responses) == len(svc)


def test_warm_ring_overwrite_reports_evictions():
    warm = tiers.init_warm(8, 4, n_clusters=2, bucket=4)
    e = jnp.asarray(_unit(np.eye(4, dtype=np.float32)))
    dem = tiers.Demoted(keys=jnp.tile(e, (2, 1)),
                        value_ids=jnp.arange(8, dtype=jnp.int32),
                        tenants=jnp.zeros(8, jnp.int32),
                        mask=jnp.ones(8, bool))
    warm, ev = tiers.warm_append(warm, dem)
    assert int((ev >= 0).sum()) == 0        # ring was empty
    dem2 = dem._replace(value_ids=jnp.arange(8, 16, dtype=jnp.int32))
    warm, ev = tiers.warm_append(warm, dem2)
    np.testing.assert_array_equal(np.sort(np.asarray(ev)), np.arange(8))


def test_warm_topk_no_duplicates_after_ring_wrap():
    """A slot overwritten after the last rebuild is reachable through a
    stale IVF member entry AND the tail window; it must be served once
    (the epoch partition), not fill two top-k ranks."""
    d = 4
    warm = tiers.init_warm(4, d, n_clusters=2, bucket=4)
    e = jnp.asarray(_unit(np.eye(4, dtype=np.float32)))

    def dem(rows, vids):
        m = len(vids)
        return tiers.Demoted(keys=e[jnp.asarray(rows)],
                             value_ids=jnp.asarray(vids, jnp.int32),
                             tenants=jnp.zeros(m, jnp.int32),
                             mask=jnp.ones(m, bool))

    warm, _ = tiers.warm_append(warm, dem([0, 1], [0, 1]))
    warm = tiers.warm_rebuild(warm, iters=2)       # slots 0,1 indexed
    # wrap the ring: slots 2,3 then 0,1 overwritten post-rebuild
    warm, _ = tiers.warm_append(warm, dem([2, 3, 0, 1], [2, 3, 4, 5]))
    q = e[:1]                                      # near slot 0's new row
    res = tiers.cascade_query(tiers.init_hot(4, d), warm, q,
                              jnp.zeros(1, jnp.int32),
                              jnp.zeros(1, jnp.float32),
                              k=2, n_probe=2, tail=4)
    s, vids = res.scores, res.value_ids
    live = np.asarray(vids[0])[np.asarray(s[0]) > -1e29]
    assert len(set(live.tolist())) == len(live), vids


# ---------------------------------------------------------------------------
# calibration fix + per-tenant thresholds
# ---------------------------------------------------------------------------

def test_calibrate_zero_negatives_no_crash():
    scores = np.asarray([0.7, 0.8, 0.9])
    labels = np.ones(3, np.int32)
    cal = calibrate_for_false_hit_budget(scores, labels)
    assert cal.false_hit_rate == 0.0
    assert cal.true_hit_rate == 1.0
    assert cal.threshold <= 0.7


def test_per_tenant_calibrated_thresholds():
    svc = CacheService(dim=8, hot_capacity=16, warm_capacity=32,
                       n_clusters=2, bucket=16, threshold=0.9)
    strict = rng.normal([0.0, 1.0], 0.1, (500, 2)).reshape(-1)
    labels = np.tile([0, 1], 500).astype(np.int32)
    cal = svc.calibrate_tenant(7, strict, labels, max_false_hit_rate=0.01)
    assert svc.policies.get(7).threshold == cal.threshold
    assert svc.policies.get(3).threshold == 0.9  # others keep the default


def test_calibrate_rescales_admission_margin_with_threshold():
    """Regression: PolicyTable.calibrate used to keep the stale
    admission_margin verbatim when the threshold moved, silently
    changing the band's width relative to the new operating point's
    paraphrase scale (TenantPolicy.with_threshold keeps
    margin/(1-threshold) constant)."""
    from repro.cache_service import PolicyTable, TenantPolicy

    table = PolicyTable(TenantPolicy(0.95, admission_margin=0.02))
    # scored pairs whose budgeted threshold lands well below 0.95
    scores = np.concatenate([rng.normal(0.6, 0.05, 400),
                             rng.normal(0.9, 0.03, 400)])
    labels = np.repeat([0, 1], 400).astype(np.int32)
    cal = table.calibrate(0, scores, labels, max_false_hit_rate=0.01)
    pol = table.get(0)
    assert pol.threshold == cal.threshold < 0.9
    expected = 0.02 * (1 - cal.threshold) / (1 - 0.95)
    assert pol.admission_margin == pytest.approx(expected)
    assert pol.admission_margin > 0.02       # looser point, wider band
    # relative width is preserved exactly
    assert pol.admission_margin / (1 - pol.threshold) \
        == pytest.approx(0.02 / (1 - 0.95))
    # degenerate old threshold ~1.0: no division blow-up, and the
    # safety caps keep the band from swallowing the score space — a
    # query with no similarity to the store must still be admitted
    t2 = PolicyTable(TenantPolicy(1.0, admission_margin=0.1))
    t2.calibrate(0, scores, labels)
    p2 = t2.get(0)
    assert 0.0 <= p2.admission_margin <= 0.5 * p2.threshold
    assert t2.admit_mask(np.zeros(1, np.int32),
                         np.zeros(1, np.float32))[0]


# ---------------------------------------------------------------------------
# serving wiring
# ---------------------------------------------------------------------------

def test_cached_service_with_tiered_backend():
    emb = HashNgramEmbedder(dim=256)
    cache = CacheService(dim=256, hot_capacity=128, warm_capacity=512,
                         n_clusters=8, bucket=128, threshold=0.80,
                         flush_size=32)
    svc = CachedLLMService(emb.embed, cache, engine=None,
                           tokenizer=HashTokenizer())
    stream = [q.text for q in make_query_stream("medical", 120, seed=0,
                                                repeat_frac=0.4)]
    for i in range(0, len(stream), 8):
        out = svc.handle(stream[i:i + 8])
        assert all(r.response is not None for r in out)
    st = svc.stats()
    assert st["hits"] + st["misses"] == 120
    assert st["hits"] > 8, st


def test_cached_service_tenants_are_isolated_end_to_end():
    emb = HashNgramEmbedder(dim=128)
    cache = CacheService(dim=128, hot_capacity=64, warm_capacity=128,
                         n_clusters=4, bucket=64, threshold=0.95)
    svc = CachedLLMService(emb.embed, cache, engine=None,
                           tokenizer=HashTokenizer())
    q = ["What are the symptoms of early stage diabetes?"]
    first = svc.handle(q, tenant=0)[0]
    assert not first.cache_hit
    assert svc.handle(q, tenant=0)[0].cache_hit          # same tenant hits
    assert not svc.handle(q, tenant=1)[0].cache_hit      # other tenant not


# ---------------------------------------------------------------------------
# the one-release flat-kwargs construction shim (CacheConfig is the v2
# surface; the v2.0-removed lookup/insert/stats shims must stay gone)
# ---------------------------------------------------------------------------

def test_flat_kwargs_shim_warns_exactly_once_per_process():
    """Legacy flat-kwargs construction warns on the first use and then
    never again in the process (the flag is class-level, not
    per-instance) — and the message points at the migration table so
    the one shot carries the whole story."""
    import warnings

    saved = CacheService._kwargs_warned
    try:
        CacheService._kwargs_warned = False
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            CacheService(dim=16, hot_capacity=8, warm_capacity=32,
                         n_clusters=2, bucket=16)
            # second construction, same process: silent
            CacheService(dim=16, hot_capacity=8, warm_capacity=32,
                         n_clusters=2, bucket=16)
        deps = [w for w in rec
                if issubclass(w.category, DeprecationWarning)
                and "CacheConfig" in str(w.message)]
        assert len(deps) == 1, [str(w.message) for w in deps]
    finally:
        CacheService._kwargs_warned = saved


def test_v2_removals_are_gone():
    """The deprecated surface announced for v2.0 must actually be
    removed: lookup/insert shims, the flat stats() view, and the
    LegacyStatsView helper class."""
    svc = CacheService(dim=16, hot_capacity=8, warm_capacity=32,
                       n_clusters=2, bucket=16)
    for name in ("lookup", "insert", "stats"):
        assert not hasattr(svc, name), name
    import repro.cache_service as cs
    assert not hasattr(cs, "LegacyStatsView")
