"""Host-RAM cold tier (DESIGN.md §12): demotion capture, budgeted
lookup + router, async promotion, tenant eviction races, and the
eviction-accounting split."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import commit_insert, plan_lookup

from repro.cache_service import (
    CacheRequest, CacheService, ColdRoutingPolicy, ColdTier, tiers,
)

rng = np.random.default_rng(29)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _service(d=16, cold_capacity=128, **kw):
    """A service small enough that the warm ring wraps quickly; the
    router margin is opened wide so uniform-random test keys (whose
    coarse centroids sit far from any query) still get fetched."""
    pol = kw.pop("cold_policy", ColdRoutingPolicy(
        min_rows_for_routing=16, n_clusters=4, route_rebuild_every=64,
        router_margin=2.0, promote_max=16))
    return CacheService(dim=d, hot_capacity=16, warm_capacity=32,
                        n_clusters=4, bucket=16, flush_size=8,
                        threshold=0.8, cold_capacity=cold_capacity,
                        cold_policy=pol, **kw)


def _fill(svc, keys, tenant=0, tag=""):
    for lo in range(0, len(keys), 8):
        plan = svc.plan(CacheRequest.build(keys[lo:lo + 8], tenant))
        svc.commit(plan, [f"r{tag}{lo + i}" for i in range(8)])
    svc.flush()


# ---------------------------------------------------------------------------
# eviction accounting split (satellite: dropped == 0 with a cold tier)
# ---------------------------------------------------------------------------

def test_no_drops_with_cold_tier_enabled():
    """Every warm-ring overwrite must be captured (demoted), never
    dropped, while the cold tier has a slot to catch it."""
    d = 16
    keys = _unit(rng.standard_normal((200, d)).astype(np.float32))
    svc = _service(d, cold_capacity=512)
    _fill(svc, keys)
    t = svc.stats_snapshot().tiers
    assert t["evictions_demoted"] > 0
    assert t["evictions_dropped"] == 0
    # the demoted strings are still alive behind the cold copies
    cold = t["cold"]
    assert cold["cold_rows"] == cold["cold_inserted"]  # ring never wrapped
    assert cold["cold_dropped"] == 0
    assert len(svc.responses) == len(svc)


def test_drops_counted_without_cold_tier():
    d = 16
    keys = _unit(rng.standard_normal((200, d)).astype(np.float32))
    svc = CacheService(dim=d, hot_capacity=16, warm_capacity=32,
                       n_clusters=4, bucket=16, flush_size=8, threshold=0.8)
    assert svc.cold is None and not svc.capabilities().cold_tier
    _fill(svc, keys)
    t = svc.stats_snapshot().tiers
    assert t["evictions_demoted"] == 0
    assert t["evictions_dropped"] > 0
    assert t["evictions_dropped"] <= t["evictions"]


def test_cold_ring_overwrites_are_the_final_drops():
    """Once the cold ring itself wraps, the overwritten rows' strings
    are freed — and only then."""
    d = 16
    keys = _unit(rng.standard_normal((240, d)).astype(np.float32))
    svc = _service(d, cold_capacity=64)
    _fill(svc, keys)
    t = svc.stats_snapshot().tiers
    assert t["evictions_dropped"] == 0
    assert t["cold"]["cold_dropped"] > 0
    assert t["evictions"] == t["cold"]["cold_dropped"]
    assert len(svc.responses) == len(svc)


# ---------------------------------------------------------------------------
# demotion LRU tie-break (satellite: insertion sequence, not slot order)
# ---------------------------------------------------------------------------

def test_demote_tie_breaks_on_insertion_sequence():
    """After a batched `hot_touch` every hit slot carries the same
    ``last_used`` clock; the demotion order must then follow the
    insertion sequence (oldest first), not the slot index — slot-order
    tie-breaking churned low-index slots under uniform traffic."""
    cap, d, m = 8, 4, 3
    keys = _unit(rng.standard_normal((cap, d)).astype(np.float32))
    hot = tiers.init_hot(cap, d)._replace(
        keys=jnp.asarray(keys), valid=jnp.ones((cap,), bool),
        tenants=jnp.zeros((cap,), jnp.int32),
        last_used=jnp.full((cap,), 7, jnp.int32),
        # insertion ages run *against* slot order: slot 7 is oldest
        inserted_at=jnp.asarray(np.arange(cap)[::-1].copy(), jnp.int32),
        value_ids=jnp.arange(cap, dtype=jnp.int32),
        clock=jnp.asarray(8, jnp.int32))
    _, dem = tiers.demote_coldest(hot, m)
    assert np.asarray(dem.mask).all()
    assert sorted(np.asarray(dem.value_ids).tolist()) == [5, 6, 7]


# ---------------------------------------------------------------------------
# ColdTier unit behavior
# ---------------------------------------------------------------------------

def test_cold_tier_budgeted_lookup_and_router():
    d, n = 16, 256
    keys = _unit(rng.standard_normal((n, d)).astype(np.float32))
    cold = ColdTier(n, d, policy=ColdRoutingPolicy(
        min_rows_for_routing=16, n_clusters=8, fetch_budget=8,
        router_margin=2.0))
    cold.bulk_load(keys, np.arange(n), np.zeros(n, np.int32))
    assert cold.centroids is not None
    q = keys[:6]
    thr = np.full(6, 0.9, np.float32)
    need = np.array([True, True, True, False, False, True])
    cf = cold.lookup(q, np.zeros(6, np.int32), thr, need)
    # only the offered rows are consulted; each exact self-match wins
    assert (cf.consulted == need).all()
    assert (cf.value_ids[need] == np.array([0, 1, 2, 5])).all()
    # int8 storage: scores within the §8 quantization bound of 1.0
    assert np.allclose(cf.scores[need], 1.0, atol=np.sqrt(d) / 254 + 1e-5)
    assert cf.scores[~need].min() <= -1e29 and (cf.value_ids[~need] == -1).all()
    assert cf.fetched_rows <= need.sum() * cold.policy.fetch_budget
    # the hits queued themselves for promotion
    assert cold.pending_promotions == int(need.sum())

    # uniform-random rows cluster badly; the calibrated gate must have
    # opened rather than falsely skipping reachable rows
    assert cold.route_slack > 0.2

    # on *tight* clusters the calibrated slack is small and the router
    # declines fetches whose best centroid sits far below threshold
    # (4 groups under 8 centroids: k-means cannot be forced to merge
    # two groups, so the fit is tight regardless of its local optimum)
    cents = _unit(rng.standard_normal((4, d)).astype(np.float32))
    tkeys = _unit(np.repeat(cents, n // 4, axis=0)
                  + 0.02 * rng.standard_normal((n, d)).astype(np.float32))
    tight = ColdTier(n, d, policy=ColdRoutingPolicy(
        min_rows_for_routing=16, n_clusters=8, router_margin=0.01))
    tight.bulk_load(tkeys, np.arange(n), np.zeros(n, np.int32))
    assert tight.route_slack < 0.2
    far = _unit(rng.standard_normal((4, d)).astype(np.float32))
    cf2 = tight.lookup(far, np.zeros(4, np.int32),
                       np.full(4, 0.99, np.float32), np.ones(4, bool))
    assert cf2.router_skips == 4 and not cf2.consulted.any()
    assert tight.stats()["cold_router_skips"] == 4  # early-exit path too


def test_cold_tier_tenant_isolation():
    d, n = 8, 64
    keys = _unit(rng.standard_normal((n, d)).astype(np.float32))
    cold = ColdTier(n, d, policy=ColdRoutingPolicy(
        min_rows_for_routing=1024, router_margin=2.0))
    cold.bulk_load(keys, np.arange(n), (np.arange(n) % 2).astype(np.int32))
    cf = cold.lookup(keys[:4], np.full(4, 1, np.int32),
                     np.full(4, 0.9, np.float32), np.ones(4, bool))
    # vids 0 and 2 belong to tenant 0: invisible to tenant 1
    assert (cf.value_ids[[1, 3]] == [1, 3]).all()
    assert not (cf.scores[[0, 2]] >= 0.9).any()


def test_take_promotions_skips_stale_entries():
    d, n = 8, 32
    keys = _unit(rng.standard_normal((n, d)).astype(np.float32))
    cold = ColdTier(n, d, policy=ColdRoutingPolicy(
        min_rows_for_routing=1024, router_margin=2.0))
    cold.bulk_load(keys, np.arange(n), np.zeros(n, np.int32))
    cold.lookup(keys[:4], np.zeros(4, np.int32),
                np.full(4, 0.9, np.float32), np.ones(4, bool))
    assert cold.pending_promotions == 4
    # tenant eviction between queueing and draining: nothing survives
    cold.evict_tenant(0)
    assert cold.pending_promotions == 0
    assert cold.take_promotions(16) is None


# ---------------------------------------------------------------------------
# end-to-end: wraparound demotion, cold hit, promotion, eviction race
# ---------------------------------------------------------------------------

def test_wraparound_demotes_to_cold_and_serves_back():
    """Rows pushed off the wrapped warm ring stay servable through the
    cold tier, and a cold hit is promoted back to warm by the next
    maintenance tick."""
    d = 16
    keys = _unit(rng.standard_normal((200, d)).astype(np.float32))
    svc = _service(d, cold_capacity=512)
    _fill(svc, keys)
    cold_vids = sorted(int(v) for v in svc.cold.value_ids[svc.cold.valid])
    assert len(cold_vids) > 100          # the ring wrapped many times
    idx = cold_vids[:8]                  # vid == insertion index here
    plan = svc.plan(CacheRequest.build(keys[idx], 0))
    assert plan.hit.all()
    assert [plan.responses[i] for i in range(8)] == [f"r{j}" for j in idx]
    s = svc.stats_snapshot()
    assert s.traffic["cold_hits"] >= 8
    assert s.tiers["cold"]["cold_fetches"] >= 8
    receipt = svc.commit(plan, [None] * 8)
    assert receipt.cold_maintenance_due
    rep = svc.maintenance()
    assert rep.cold_promoted >= 8
    # promoted rows now answer from the device tiers
    plan2 = svc.plan(CacheRequest.build(keys[idx], 0))
    assert plan2.hit.all()
    t2 = svc.stats_snapshot()
    assert t2.traffic["hot_hits"] + t2.traffic["warm_hits"] >= 8
    assert t2.tiers["evictions_dropped"] == 0


def test_commit_receipt_reports_cold_demotions():
    d = 16
    keys = _unit(rng.standard_normal((96, d)).astype(np.float32))
    svc = _service(d, cold_capacity=256)
    demoted = 0
    for lo in range(0, len(keys), 8):
        plan = svc.plan(CacheRequest.build(keys[lo:lo + 8], 0))
        demoted += svc.commit(plan,
                              [f"r{lo + i}" for i in range(8)]).demoted_cold
    svc.flush()
    assert demoted + svc.stats_snapshot().tiers["cold"]["cold_inserted"] \
        >= svc.cold.n_inserted
    assert svc.cold.n_inserted > 0


def test_evict_tenant_between_cold_hit_and_maintenance():
    """Mirror of the §7 plan/commit race one level down: a tenant
    evicted after a cold hit queued its promotion must not resurrect
    through the maintenance drain, and its host strings are freed."""
    d = 16
    keys = _unit(rng.standard_normal((200, d)).astype(np.float32))
    svc = _service(d, cold_capacity=512)
    _fill(svc, keys, tenant=0)
    other = _unit(rng.standard_normal((8, d)).astype(np.float32))
    commit_insert(svc, other, [f"t1-{i}" for i in range(8)], tenant=1)
    cold_vids = sorted(int(v) for v in svc.cold.value_ids[svc.cold.valid])
    plan = svc.plan(CacheRequest.build(keys[cold_vids[:8]], 0))
    assert plan.hit.all() and svc.cold.pending_promotions >= 8

    assert svc.evict_tenant(0) > 0       # the race
    rep = svc.maintenance()
    assert rep.cold_promoted == 0        # nothing resurrected
    assert svc.cold.pending_promotions == 0
    plan2 = svc.plan(CacheRequest.build(keys[cold_vids[:8]], 0))
    assert not plan2.hit.any()
    # tenant 1 is untouched; tenant 0's strings are gone
    assert sorted(svc.responses.values()) == [f"t1-{i}" for i in range(8)]
    hit, _, vals = plan_lookup(svc, other, tenant=1)
    assert hit.all() and all(v.startswith("t1-") for v in vals)


def test_cold_with_warm_block_streaming():
    """A cold tier behind the warm ring only adds hits: its verdicts
    are a superset of the warm-only service's on served keys."""
    d = 16
    keys = _unit(rng.standard_normal((120, d)).astype(np.float32))
    svc = _service(d, cold_capacity=256)
    _fill(svc, keys)
    base = CacheService(dim=d, hot_capacity=16, warm_capacity=32,
                        n_clusters=4, bucket=16, flush_size=8,
                        threshold=0.8)
    _fill(base, keys)
    q = np.concatenate([keys[100:110],
                        _unit(rng.standard_normal((6, d)).astype(np.float32))])
    p_cold = svc.plan(CacheRequest.build(q, 0))
    p_base = base.plan(CacheRequest.build(q, 0))
    # cold-enabled hits are a superset of warm-only hits on served keys
    assert (p_cold.hit | ~p_base.hit).all() or p_base.hit.sum() == 0
    assert not p_cold.hit[10:].any()     # random queries never hit


def test_sharded_plus_cold_rejected():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="unsharded"):
        CacheService(dim=8, mesh=mesh, cold_capacity=64)
