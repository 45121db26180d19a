"""Property tests for TTL/staleness eviction (DESIGN.md §14.2) and the
conformal hit-calibration floor (§14.3).

The TTL properties run against a real ``CacheService`` under an
injectable logical clock (``StalenessConfig.clock``), with a hot tier
squeezed small enough that entries demote through warm mid-life — the
invariants must hold wherever an entry happens to live:

  * an expired entry is NEVER served, from any tier, fp32 or int8 warm,
    whether or not maintenance has reaped it yet (plan-time masking);
  * reaping never frees a live value_id — every unexpired entry is
    still served with its own response after any maintenance;
  * ``evict_tenant`` composes with pending expiry: evicting one tenant
    neither resurrects nor double-frees the other's entries.

The container ships no ``hypothesis``; when it is importable each
property runs under ``@given``, otherwise as a deterministic seed
sweep (same predicate, fixed draw per seed — do not pip install)."""
import numpy as np
import pytest

from repro.cache_service import (
    CacheConfig, CachePlan, CacheRequest, CacheService, ConformalWindow,
    LearningConfig, StalenessConfig, TieringConfig,
)
from repro.cache_service.feedback import (
    FeedbackAccumulator, FeedbackConfig,
)
from repro.cache_service.policy import PolicyTable, TenantPolicy

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # image has none
    HAVE_HYPOTHESIS = False


def _property(f):
    if HAVE_HYPOTHESIS:
        return settings(max_examples=10, deadline=None)(
            given(seed=st.integers(0, 2**31 - 1))(f))
    return pytest.mark.parametrize("seed", range(10))(f)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _svc(clock, *, warm_dtype="float32", dim=16):
    # low flush watermark + small insert batches: every hot overflow
    # goes through the maintenance *demotion* path into warm (an
    # insert-time overflow hard-drops instead, which is legitimate
    # cache eviction but would make "live rows keep serving" vacuous)
    return CacheService(CacheConfig(
        dim=dim, threshold=0.9,
        tiering=TieringConfig(hot_capacity=8, warm_capacity=64,
                              n_clusters=2, bucket=32, n_probe=2,
                              flush_watermark=0.5, flush_size=4,
                              warm_dtype=warm_dtype),
        staleness=StalenessConfig(clock=lambda: clock["t"])))


def _commit_rows(svc, embs, responses, ttl, tenant=0):
    req = CacheRequest.build(embs, tenant, ttl=ttl)
    plan = CachePlan.for_insert(req, np.ones(len(req), bool),
                                epoch=svc._epoch,
                                embed_version=svc._embed_version)
    return svc.commit(plan, responses)


def _ttl_world(seed, clock, warm_dtype="float32"):
    """Random TTL pattern over enough rows to push through hot into
    warm; returns (svc, embs, deadlines)."""
    rng = np.random.default_rng(seed)
    svc = _svc(clock, warm_dtype=warm_dtype)
    n = int(rng.integers(12, 28))      # > hot_capacity=8: forces demotion
    embs = _unit(rng.standard_normal((n, 16)).astype(np.float32))
    ttl = np.where(rng.random(n) < 0.6,
                   rng.uniform(1.0, 20.0, n), np.inf).astype(np.float32)
    deadlines = clock["t"] + ttl
    for lo in range(0, n, 2):          # small batches interleave demotions
        hi = min(lo + 2, n)
        _commit_rows(svc, embs[lo:hi], [f"r{i}" for i in range(lo, hi)],
                     ttl[lo:hi])
        svc.maintenance()              # flush to warm + publish the IVF
    return svc, embs, deadlines


@_property
def test_expired_rows_never_served_any_tier(seed):
    warm_dtype = ("float32", "int8")[seed % 2]
    clock = {"t": 100.0}
    svc, embs, deadlines = _ttl_world(seed, clock, warm_dtype)
    rng = np.random.default_rng(seed + 1)
    for now in sorted(rng.uniform(100.0, 125.0, 4)):
        clock["t"] = float(now)
        plan = svc.plan(CacheRequest.build(embs), coalesce=False)
        hit = np.asarray(plan.hit)
        live = deadlines >= now
        # an expired row must never hit — masked at plan time even
        # though maintenance hasn't reaped anything yet
        assert not np.any(hit & ~live), (
            f"expired row served at t={now} ({warm_dtype}): "
            f"{np.flatnonzero(hit & ~live)}")
        # and every still-live row must still be served with its own
        # response (expiry must not over-mask live neighbours)
        assert np.all(hit[live]), (
            f"live row lost at t={now}: {np.flatnonzero(live & ~hit)}")
        for i in np.flatnonzero(live):
            assert plan.responses[i] == f"r{i}"


@_property
def test_reaping_never_frees_live_value_id(seed):
    clock = {"t": 0.0}
    svc, embs, deadlines = _ttl_world(seed, clock)
    rng = np.random.default_rng(seed + 2)
    for now in sorted(rng.uniform(0.0, 30.0, 5)):
        clock["t"] = float(now)
        svc.maintenance()              # reap everything expired by now
        live = deadlines >= now
        plan = svc.plan(CacheRequest.build(embs), coalesce=False)
        hit = np.asarray(plan.hit)
        assert np.all(hit[live]), (
            f"maintenance at t={now} reaped live row(s) "
            f"{np.flatnonzero(live & ~hit)}")
        for i in np.flatnonzero(live):
            assert plan.responses[i] == f"r{i}", \
                f"row {i} value freed while its deadline is in the future"
    clock["t"] = 1e9                   # every finite deadline passes...
    svc.maintenance()
    # ...freeing exactly the finite-TTL values; no-TTL rows live on
    assert len(svc.responses) == int(np.isinf(deadlines).sum())


@_property
def test_evict_tenant_composes_with_pending_expiry(seed):
    rng = np.random.default_rng(seed)
    clock = {"t": 0.0}
    svc = _svc(clock)
    n = 12
    e0 = _unit(rng.standard_normal((n, 16)).astype(np.float32))
    e1 = _unit(rng.standard_normal((n, 16)).astype(np.float32))
    ttl = np.where(rng.random(n) < 0.5, 5.0, np.inf).astype(np.float32)
    for lo in range(0, n, 2):          # through the flush path; no drops
        hi = min(lo + 2, n)
        _commit_rows(svc, e0[lo:hi], [f"a{i}" for i in range(lo, hi)],
                     ttl[lo:hi], tenant=0)
        _commit_rows(svc, e1[lo:hi], [f"b{i}" for i in range(lo, hi)],
                     ttl[lo:hi], tenant=1)
        svc.maintenance()
    clock["t"] = 10.0                  # finite-TTL rows now pending-expired
    svc.evict_tenant(0)
    svc.maintenance()                  # reap must not double-free t0 rows
    plan0 = svc.plan(CacheRequest.build(e0, 0), coalesce=False)
    assert not np.asarray(plan0.hit).any(), "evicted tenant still served"
    plan1 = svc.plan(CacheRequest.build(e1, 1), coalesce=False)
    hit1 = np.asarray(plan1.hit)
    live = np.isinf(ttl)
    assert np.all(hit1[live]), "tenant eviction dropped the other tenant"
    assert not np.any(hit1[~live]), "expired row of tenant 1 served"
    for i in np.flatnonzero(live):
        assert plan1.responses[i] == f"b{i}"
    # exactly tenant 1's live values remain held
    assert len(svc.responses) == int(live.sum())


@_property
def test_cold_tier_respects_expiry(seed):
    """Entries pushed all the way into the host-RAM cold tier must
    still honour their deadline on the routed fetch path."""
    rng = np.random.default_rng(seed)
    clock = {"t": 0.0}
    svc = CacheService(CacheConfig(
        dim=16, threshold=0.9,
        tiering=TieringConfig(hot_capacity=8, warm_capacity=16,
                              n_clusters=2, bucket=8, n_probe=2,
                              cold_capacity=128),
        staleness=StalenessConfig(clock=lambda: clock["t"])))
    n = 40                             # >> hot+warm: spills into cold
    embs = _unit(rng.standard_normal((n, 16)).astype(np.float32))
    ttl = np.where(rng.random(n) < 0.5, 4.0, np.inf).astype(np.float32)
    for lo in range(0, n, 8):
        hi = min(lo + 8, n)
        _commit_rows(svc, embs[lo:hi], [f"r{i}" for i in range(lo, hi)],
                     ttl[lo:hi])
        svc.maintenance()
    clock["t"] = 6.0
    svc.maintenance()
    plan = svc.plan(CacheRequest.build(embs), coalesce=False)
    hit = np.asarray(plan.hit)
    assert not np.any(hit[np.isfinite(ttl)]), \
        "expired row served (cold-backed tiering)"


# ---------------------------------------------------------------------------
# §14.3 conformal floor
# ---------------------------------------------------------------------------

def test_conformal_window_floor_is_order_statistic():
    w = ConformalWindow(capacity=64)
    for s in np.linspace(0.0, 0.63, 64):
        w.add(float(s))
    # alpha=0.25 over n=64: rank = ceil(65*0.75) = 49 -> 49th smallest
    scores = np.sort(w.scores[:w.fill])
    assert w.floor(0.25) == pytest.approx(scores[48] + 1e-6)
    # tiny alpha clamps to the max
    assert w.floor(1e-6) == pytest.approx(scores[-1] + 1e-6)


def test_conformal_window_is_recency_ring():
    w = ConformalWindow(capacity=8)
    for s in [0.9] * 8:                # old era: high negatives
        w.add(s)
    for s in [0.1] * 8:                # new era fully ages it out
        w.add(s)
    assert w.floor(0.3) < 0.2          # floor tracks the current era


def test_hit_audit_feeds_window_and_raises_floor():
    fb = FeedbackAccumulator(FeedbackConfig(conformal_min=8,
                                            max_false_hit_rate=0.05))
    for _ in range(16):
        fb.observe(0, 0.4, duplicate=False, admitted=True)
    low = fb.conformal_floor(0)
    assert low is not None and low < 0.5
    # audited FALSE hits above the threshold de-censor the stream...
    for _ in range(16):
        fb.observe_hit_audit(0, 0.8, duplicate=False)
    assert fb.conformal_floor(0) > 0.7
    assert fb.counters["hit_audits"] == 16
    assert fb.counters["audited_false_hits"] == 16
    # ...while audited TRUE hits never move the negative window
    before = fb.conformal_floor(0)
    for _ in range(16):
        fb.observe_hit_audit(0, 0.99, duplicate=True)
    assert fb.conformal_floor(0) == pytest.approx(before)


def test_effective_thresholds_only_ever_raise():
    fb = FeedbackAccumulator(FeedbackConfig(conformal_min=4))
    pol = PolicyTable(TenantPolicy(threshold=0.85))
    for _ in range(8):
        fb.observe(0, 0.95, duplicate=False, admitted=True)  # hostile band
        fb.observe(1, 0.10, duplicate=False, admitted=True)  # benign band
    eff = pol.effective_thresholds(np.asarray([0, 1, 2]), fb)
    assert eff[0] > 0.9                # floor raised above the policy
    assert eff[1] == pytest.approx(0.85)   # benign floor can't lower it
    assert eff[2] == pytest.approx(0.85)   # unseen tenant: no floor
    assert np.all(eff >= pol.thresholds_for(np.asarray([0, 1, 2])))
