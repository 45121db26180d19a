"""Golden parity: the coalesced plan/commit pipeline is bit-exact with
the naive two-call serving loop (uncoalesced per-batch plan, then a
fresh for_insert commit of the misses — the v2.0-removed lookup/insert
shims, inlined) — hits, scores, value ids, admissions, evictions and
the full device tier state — for both backends (SemanticCache and
CacheService) and both warm panels (fp32 and int8).  The query
mix includes exact in-batch duplicates, so miss coalescing is exercised
while keeping even the host strings identical."""
import numpy as np
import pytest
from conftest import commit_insert, plan_lookup

from repro.cache_service import CachePlan, CacheRequest, CacheService
from repro.core import SemanticCache

rng = np.random.default_rng(29)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _batches(d, n_batches=8, batch=8, repeat_frac=0.4):
    """Query stream with cross-batch repeats and exact in-batch dups."""
    seen = []
    out = []
    for b in range(n_batches):
        rows = []
        for i in range(batch - 1):
            if seen and rng.random() < repeat_frac:
                rows.append(seen[rng.integers(len(seen))])
            else:
                e = _unit(rng.standard_normal(d).astype(np.float32))
                seen.append(e)
                rows.append(e)
        rows.append(rows[0])        # exact duplicate within the batch
        out.append(np.stack(rows))
    return out


def _two_call_serve(cache, embs, tenant, tenant_aware):
    """The naive serving loop: one uncoalesced read plan, generate
    every miss, commit them through a fresh for_insert plan with the
    observed scores (exactly what the removed lookup/insert shims
    did)."""
    if tenant_aware:
        hits, scores, values = plan_lookup(cache, embs, tenant=tenant)
    else:
        plan = cache.plan(CacheRequest.build(np.asarray(embs)),
                          coalesce=False)
        hits, scores, values = plan.hit, plan.scores, plan.responses
    miss = [i for i, h in enumerate(hits) if not h]
    if miss:
        answers = [f"gen({embs[i].tobytes().hex()[:12]})" for i in miss]
        sel = np.asarray(miss)
        if tenant_aware:
            commit_insert(cache, embs[sel], answers, tenant=tenant,
                          scores=scores[sel])
        else:
            req = CacheRequest.build(np.asarray(embs[sel]))
            cache.commit(CachePlan.for_insert(
                req, np.ones(len(req), bool)), answers)
    return np.asarray(hits), np.asarray(scores), values


def _plan_commit_serve(cache, embs, tenant):
    """The typed pipeline: plan -> one generation per miss-group leader
    -> commit."""
    plan = cache.plan(CacheRequest.build(embs, tenant))
    responses = [None] * len(embs)
    for i in plan.miss_rows():
        lead = int(plan.miss_leader[i])
        responses[int(i)] = f"gen({embs[lead].tobytes().hex()[:12]})"
    cache.commit(plan, responses)
    return plan.hit, plan.scores, plan.responses


def _assert_tree_equal(a, b, names):
    for name, x, y in zip(names, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def _parity_counts(svc):
    s = svc.stats_snapshot()
    return {"lookups": s.traffic["lookup_rows"],
            "hot_hits": s.traffic["hot_hits"],
            "warm_hits": s.traffic["warm_hits"],
            "inserts": s.admission["admitted"],
            "admission_skips": s.admission["skipped"],
            "demotions": s.tiers["demotions"],
            "rebuilds": s.rebuild["rebuilds"],
            "evictions": s.tiers["evictions"]}


@pytest.mark.parametrize("warm_dtype", ["float32", "int8"])
def test_cache_service_plan_commit_matches_two_call_loop(warm_dtype):
    d = 24
    mk = lambda: CacheService(
        dim=d, hot_capacity=16, warm_capacity=64, n_clusters=4, bucket=32,
        n_probe=4, threshold=0.85, admission_margin=0.05, flush_size=8,
        rebuild_every=2, warm_dtype=warm_dtype)
    naive, typed = mk(), mk()
    for b, embs in enumerate(_batches(d)):
        tenant = b % 3
        lh, ls, lv = _two_call_serve(naive, embs, tenant,
                                     tenant_aware=True)
        th, ts, tv = _plan_commit_serve(typed, embs, tenant)
        np.testing.assert_array_equal(lh, th, err_msg=f"batch {b} hits")
        np.testing.assert_array_equal(ls, ts, err_msg=f"batch {b} scores")
        assert lv == tv, f"batch {b} hit responses"
        # full device-state parity after every batch: same admissions,
        # same value-id assignment, same demotions/evictions
        _assert_tree_equal(naive.hot, typed.hot,
                           [f"hot.{f}" for f in naive.hot._fields])
        _assert_tree_equal(naive.warm, typed.warm,
                           [f"warm.{f}" for f in naive.warm._fields])
        assert naive.responses == typed.responses, f"batch {b}"
    assert _parity_counts(naive) == _parity_counts(typed)


def test_semantic_cache_plan_commit_matches_two_call_loop():
    d = 24
    naive = SemanticCache(capacity=64, dim=d, threshold=0.85)
    typed = SemanticCache(capacity=64, dim=d, threshold=0.85)
    for b, embs in enumerate(_batches(d)):
        lh, ls, lv = _two_call_serve(naive, embs, 0, tenant_aware=False)
        th, ts, tv = _plan_commit_serve(typed, embs, 0)
        np.testing.assert_array_equal(lh, th, err_msg=f"batch {b} hits")
        np.testing.assert_array_equal(ls, ts, err_msg=f"batch {b} scores")
        assert lv == tv
        _assert_tree_equal(naive.state, typed.state,
                           [f"state.{f}" for f in naive.state._fields])
        assert naive.responses == typed.responses
    assert naive.stats_snapshot()["inserts"] \
        == typed.stats_snapshot()["inserts"]


def test_for_insert_plan_applies_admission_like_serve_path():
    """Committing through a for_insert plan (the helper the removed
    insert shim compiled down to) must admit exactly the rows the
    policy's admission mask selects, and leave identical device
    state to an explicit for_insert commit."""
    d = 16
    a = CacheService(dim=d, hot_capacity=16, warm_capacity=32, n_clusters=2,
                     bucket=16, threshold=0.9, admission_margin=0.1)
    b = CacheService(dim=d, hot_capacity=16, warm_capacity=32, n_clusters=2,
                     bucket=16, threshold=0.9, admission_margin=0.1)
    e = _unit(rng.standard_normal((6, d)).astype(np.float32))
    scores = np.asarray([0.0, 0.85, 0.3, 0.95, 0.5, 0.82], np.float32)
    n_a = commit_insert(a, e, [f"r{i}" for i in range(6)], tenant=1,
                        scores=scores)
    req = CacheRequest.build(e, 1)
    admit = b.policies.admit_mask(req.tenants, scores)
    n_b = b.commit(CachePlan.for_insert(req, admit, scores),
                   [f"r{i}" for i in range(6)]).admitted
    assert n_a == n_b == int(admit.sum()) < 6
    _assert_tree_equal(a.hot, b.hot, a.hot._fields)
    assert a.responses == b.responses
