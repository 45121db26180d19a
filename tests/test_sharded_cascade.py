"""Sharded warm tier (DESIGN.md §8): shard_map-vs-oracle and
sharded-vs-single-device parity for `cascade_query` (jnp cascade and
Pallas kernel, fp32 and int8) across 1/2/8 virtual devices, the shared
local-topk/tiny-merge helper, the quantization error bound, a
`warm_publish_index` swap mid-stream and `evict_tenant` on a sharded
warm tier.  Multi-device cases need
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the dedicated
CI job); below that device count they skip, the single-device cases
always run."""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import commit_insert, plan_lookup
from jax.sharding import PartitionSpec as P

from repro.cache_service import CacheService, tiers
from repro.core import ivf as ivf_lib
from repro.core.distrib import merge_local_topk, merge_stacked_topk
from repro.launch.mesh import make_host_mesh

rng = np.random.default_rng(11)

N_DEV = len(jax.devices())


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _need_devices(n):
    if N_DEV < n:
        pytest.skip(f"needs {n} devices, have {N_DEV} (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _hot(Nh=40, D=16, n_tenants=3):
    hk = jnp.asarray(_unit(rng.standard_normal((Nh, D)).astype(np.float32)))
    return tiers.init_hot(Nh, D)._replace(
        keys=hk, valid=jnp.asarray(rng.random(Nh) > 0.3),
        tenants=jnp.asarray(rng.integers(0, n_tenants, Nh), jnp.int32),
        value_ids=jnp.asarray(rng.integers(0, 1000, Nh), jnp.int32))


def _warm_shard(cap, D, K, bucket, n_tenants=3, unindexed=6, vid_base=1000):
    wk = jnp.asarray(_unit(rng.standard_normal((cap, D)).astype(np.float32)))
    wv = jnp.asarray(rng.random(cap) > 0.2)
    cent = ivf_lib.kmeans(wk, wv, K, 4, 0)
    members, sizes = ivf_lib.build_lists(wk, wv, cent, bucket)
    w = tiers.init_warm(cap, D, K, bucket)._replace(
        keys=wk, valid=wv,
        tenants=jnp.asarray(rng.integers(0, n_tenants, cap), jnp.int32),
        # unique per shard (and across shards via vid_base spacing) so
        # tests may invert value id -> row
        value_ids=jnp.asarray(vid_base + rng.permutation(1000)[:cap],
                              jnp.int32),
        write_seq=jnp.asarray(rng.permutation(cap) + 1, jnp.int32),
        cursor=jnp.asarray(int(rng.integers(0, cap)), jnp.int32),
        total=jnp.asarray(cap, jnp.int32), centroids=cent, members=members,
        sizes=sizes, indexed_total=jnp.asarray(cap - unindexed, jnp.int32))
    return tiers.requantize(w)


def _swarm(S, cap=32, D=16, K=4, bucket=8, **kw):
    return tiers.stack_warm(
        [_warm_shard(cap, D, K, bucket, vid_base=1000 + 1000 * s, **kw)
         for s in range(S)])


def _queries(n_q, D, n_tenants=3):
    q = jnp.asarray(_unit(rng.standard_normal((n_q, D)).astype(np.float32)))
    qt = jnp.asarray(rng.integers(0, n_tenants, n_q), jnp.int32)
    thr = jnp.asarray(rng.uniform(0.2, 0.9, n_q).astype(np.float32))
    return q, qt, thr


def _assert_same(a, b, fields=tiers.CascadeResult._fields):
    for name in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


def _shard_put(swarm, mesh):
    """Lay the stacked warm state out on the mesh (leading axis over
    `model`) so lookups read resident shards instead of resharding."""
    return tiers.place_warm_sharded(swarm, mesh)


# ---------------------------------------------------------------------------
# shared merge helper
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [1, 2])
def test_merge_helper_collective_matches_stacked_and_concat(S):
    _need_devices(S)
    mesh = make_host_mesh(1, S)
    k, Q = 3, 5
    s = jnp.asarray(rng.standard_normal((S, Q, k)).astype(np.float32))
    pay = jnp.asarray(rng.integers(0, 99, (S, Q, k)), jnp.int32)

    sm_o, pm_o = merge_stacked_topk(k, s, pay)
    # the stacked oracle == lax.top_k over the shard-major concat
    flat_s = jnp.moveaxis(s, 0, 1).reshape(Q, S * k)
    flat_p = jnp.moveaxis(pay, 0, 1).reshape(Q, S * k)
    sm_ref, im = jax.lax.top_k(flat_s, k)
    rows = jnp.arange(Q)[:, None]
    np.testing.assert_array_equal(np.asarray(sm_o), np.asarray(sm_ref))
    np.testing.assert_array_equal(np.asarray(pm_o),
                                  np.asarray(flat_p[rows, im]))

    fn = jax.shard_map(
        lambda sl, pl: merge_local_topk(
            "model", k, sl.reshape(Q, k), pl.reshape(Q, k)),
        mesh=mesh, in_specs=(P("model"), P("model")),
        out_specs=(P(), P()), check_vma=False)
    sm_c, pm_c = jax.jit(fn)(s, pay)
    np.testing.assert_array_equal(np.asarray(sm_c), np.asarray(sm_o))
    np.testing.assert_array_equal(np.asarray(pm_c), np.asarray(pm_o))


def test_merge_helper_ties_resolve_to_earliest_shard():
    S, Q, k = 3, 2, 2
    s = jnp.ones((S, Q, k), jnp.float32)          # all-tied scores
    pay = jnp.arange(S * Q * k, dtype=jnp.int32).reshape(S, Q, k)
    sm, pm = merge_stacked_topk(k, s, pay)
    # winners must be shard 0's candidates, in candidate order
    np.testing.assert_array_equal(np.asarray(pm), np.asarray(pay[0]))
    assert float(jnp.min(sm)) == 1.0


# ---------------------------------------------------------------------------
# sharded cascade: oracle vs shard_map, sharded vs single-device
# ---------------------------------------------------------------------------

def test_sharded_oracle_s1_equals_plain_single_device():
    """One shard IS the single-device cascade: the stacked schedule at
    S=1 must be bit-exact with the plain path, jnp and kernel."""
    hot = _hot()
    warm = _warm_shard(64, 16, 8, 16)
    swarm = jax.tree_util.tree_map(lambda x: x[None], warm)
    q, qt, thr = _queries(9, 16)
    for uk in (False, True):
        plain = tiers.cascade_query(hot, warm, q, qt, thr, k=2, n_probe=4,
                                    tail=10, use_kernel=uk)
        stacked = tiers.cascade_query(hot, swarm, q, qt, thr, k=2, n_probe=4,
                                      tail=10, use_kernel=uk)
        _assert_same(plain, stacked)


@pytest.mark.parametrize("S", [1, 2, 8])
@pytest.mark.parametrize("use_kernel,quantized", [(False, False),
                                                  (True, False),
                                                  (True, True)])
def test_shard_map_matches_single_device_oracle(S, use_kernel, quantized):
    """The distributed schedule (shard_map + all-gather merge) is
    bit-exact with its single-device emulation — partial probes, tail
    windows, invalid slots and mixed tenants included."""
    _need_devices(S)
    hot = _hot()
    swarm = _swarm(S)
    q, qt, thr = _queries(9, 16)
    mesh = make_host_mesh(1, S)
    oracle = tiers.cascade_query(hot, swarm, q, qt, thr, k=2, n_probe=2,
                                 tail=5, use_kernel=use_kernel,
                                 quantized=quantized)
    dist = jax.jit(lambda h, w, qq, t, th: tiers.cascade_query(
        h, w, qq, t, th, k=2, n_probe=2, tail=5, use_kernel=use_kernel,
        quantized=quantized, mesh=mesh))(
            hot, _shard_put(swarm, mesh), q, qt, thr)
    _assert_same(oracle, dist)


@pytest.mark.parametrize("S", [2, 8])
def test_sharded_fused_bitexact_vs_single_device_unfused_full_probe(S):
    """The acceptance parity: the kernel's sharded cascade on S virtual
    devices reproduces the single-device served path bit-for-bit at
    fp32 (scores, value ids, hit masks) when both sides probe their
    full cluster sets over the same row universe."""
    _need_devices(S)
    D, cap, k = 16, 32, 2
    hot = _hot(D=D)
    # one row universe, partitioned contiguously over shards; every row
    # indexed (no tail) so full-probe candidate sets coincide exactly
    keys = _unit(rng.standard_normal((S * cap, D)).astype(np.float32))
    valid = rng.random(S * cap) > 0.2
    tenants = rng.integers(0, 3, S * cap).astype(np.int32)
    vids = np.arange(1000, 1000 + S * cap, dtype=np.int32)

    def plain_warm():
        wk, wv = jnp.asarray(keys), jnp.asarray(valid)
        cent = ivf_lib.kmeans(wk, wv, 8, 4, 0)
        members, sizes = ivf_lib.build_lists(wk, wv, cent, S * cap)
        return tiers.requantize(tiers.init_warm(S * cap, D, 8, S * cap)
                                ._replace(
            keys=wk, valid=wv, tenants=jnp.asarray(tenants),
            value_ids=jnp.asarray(vids),
            write_seq=jnp.arange(1, S * cap + 1, dtype=jnp.int32),
            cursor=jnp.zeros((), jnp.int32),
            total=jnp.asarray(S * cap, jnp.int32), centroids=cent,
            members=members, sizes=sizes,
            indexed_total=jnp.asarray(S * cap, jnp.int32)))

    def shard(s):
        sl = slice(s * cap, (s + 1) * cap)
        wk, wv = jnp.asarray(keys[sl]), jnp.asarray(valid[sl])
        cent = ivf_lib.kmeans(wk, wv, 2, 4, s)
        members, sizes = ivf_lib.build_lists(wk, wv, cent, cap)
        return tiers.requantize(tiers.init_warm(cap, D, 2, cap)._replace(
            keys=wk, valid=wv, tenants=jnp.asarray(tenants[sl]),
            value_ids=jnp.asarray(vids[sl]),
            write_seq=jnp.arange(1, cap + 1, dtype=jnp.int32),
            cursor=jnp.zeros((), jnp.int32),
            total=jnp.asarray(cap, jnp.int32), centroids=cent,
            members=members, sizes=sizes,
            indexed_total=jnp.asarray(cap, jnp.int32)))

    q, qt, thr = _queries(16, D)
    mesh = make_host_mesh(1, S)
    single = tiers.cascade_query(hot, plain_warm(), q, qt, thr, k=k,
                                 n_probe=8, tail=0)
    swarm = _shard_put(tiers.stack_warm([shard(s) for s in range(S)]), mesh)
    dist = jax.jit(lambda h, w, qq, t, th: tiers.cascade_query(
        h, w, qq, t, th, k=k, n_probe=2, tail=0, use_kernel=True,
        mesh=mesh))(hot, swarm, q, qt, thr)
    _assert_same(single, dist)


@pytest.mark.parametrize("S", [2])
def test_cross_shard_collective_is_k_shards_not_corpus(S):
    """The only cross-shard collectives in the sharded lookup move
    (Q, k·S)-scale candidate panels (+ the (Q,) hot-slot psum), never a
    corpus-sized (Q, N) score matrix."""
    _need_devices(S)
    cap, Q, k = 256, 8, 2
    hot = _hot()
    swarm = _swarm(S, cap=cap, K=4, bucket=32)
    q, qt, thr = _queries(Q, 16)
    mesh = make_host_mesh(1, S)
    fn = jax.jit(lambda h, w, qq, t, th: tiers.cascade_query(
        h, w, qq, t, th, k=k, n_probe=2, tail=4, use_kernel=True,
        mesh=mesh))
    txt = fn.lower(hot, _shard_put(swarm, mesh), q, qt, thr) \
            .compile().as_text()
    # HLO shape syntax: `%x = f32[8,4]{0,1} all-gather(...)`
    gathers = re.findall(r"=\s*\w+\[([\d,]+)\]\S*\s+all-(?:gather|reduce)\(",
                         txt)
    if not gathers:                      # collectives elided / renamed
        pytest.skip("no all-gather in compiled HLO to inspect")
    biggest = max(int(np.prod([int(d) for d in dims.split(",")]))
                  for dims in gathers)
    assert biggest <= Q * k * S, \
        f"collective of {biggest} elements (> Q*k*S = {Q * k * S})"
    assert biggest < Q * cap, "corpus-scale collective leaked into lookup"


# ---------------------------------------------------------------------------
# int8 quantized warm panel
# ---------------------------------------------------------------------------

def test_quantize_rows_error_bound():
    keys = jnp.asarray(_unit(rng.standard_normal((256, 64)
                                                 ).astype(np.float32)))
    q8, sc = tiers.quantize_rows(keys)
    assert q8.dtype == jnp.int8
    recon = q8.astype(jnp.float32) * sc[:, None]
    # per-component: |k - s*q8| <= s/2; cosine vs any unit query is
    # within amax*sqrt(D)/254 (DESIGN.md §8)
    amax = jnp.max(jnp.abs(keys), axis=-1)
    D = keys.shape[1]
    assert float(jnp.max(jnp.abs(recon - keys)
                         / (sc[:, None] / 2 + 1e-12))) <= 1.0 + 1e-3
    q = jnp.asarray(_unit(rng.standard_normal((32, 64)).astype(np.float32)))
    err = jnp.abs(q @ keys.T - q @ recon.T)
    bound = amax * np.sqrt(D) / 254.0
    assert float(jnp.max(err - bound[None, :])) <= 1e-6


def test_int8_scores_are_exact_rescored_cosines():
    """Whatever the quantized scan *selects*, the scores the cascade
    returns must be true fp32 cosines of the selected rows."""
    hot = _hot(Nh=8)
    hot = hot._replace(valid=jnp.zeros_like(hot.valid))   # warm-only
    warm = _warm_shard(64, 16, 4, 16, unindexed=0)
    q, qt, _ = _queries(12, 16)
    thr = jnp.full((12,), -1.0, jnp.float32)
    res = tiers.cascade_query(hot, warm, q, qt, thr, k=2, n_probe=4,
                              tail=0, use_kernel=True, quantized=True)
    vids = np.asarray(res.value_ids)
    scores = np.asarray(res.scores)
    wkeys = np.asarray(warm.keys)
    wvids = np.asarray(warm.value_ids)
    qn = np.asarray(q)
    for r in range(12):
        for c in range(2):
            if vids[r, c] < 0:
                continue
            row = int(np.nonzero(wvids == vids[r, c])[0][0])
            exact = float(qn[r] @ wkeys[row])
            assert abs(scores[r, c] - exact) < 1e-5


def test_int8_recall_parity_on_clustered_corpus():
    """On the cache's actual workload (paraphrase clusters, clear
    margins) the quantized scan selects the same hits as fp32."""
    D, n = 32, 512
    cents = _unit(rng.standard_normal((8, D)).astype(np.float32))
    keys = _unit(np.repeat(cents, n // 8, axis=0)
                 + 0.15 * rng.standard_normal((n, D)).astype(np.float32))
    wk = jnp.asarray(keys)
    wv = jnp.ones((n,), bool)
    cent = ivf_lib.kmeans(wk, wv, 8, 4, 0)
    members, sizes = ivf_lib.build_lists(wk, wv, cent, n // 4)
    warm = tiers.requantize(tiers.init_warm(n, D, 8, n // 4)._replace(
        keys=wk, valid=wv, tenants=jnp.zeros((n,), jnp.int32),
        value_ids=jnp.arange(n, dtype=jnp.int32),
        write_seq=jnp.arange(1, n + 1, dtype=jnp.int32),
        total=jnp.asarray(n, jnp.int32),
        centroids=cent, members=members, sizes=sizes,
        indexed_total=jnp.asarray(n, jnp.int32)))
    hot = tiers.init_hot(16, D)
    idx = rng.choice(n, 64, replace=False)
    q = jnp.asarray(_unit(keys[idx] + 0.05 * rng.standard_normal(
        (64, D)).astype(np.float32)))
    qt = jnp.zeros((64,), jnp.int32)
    thr = jnp.full((64,), 0.9, jnp.float32)
    fp32 = tiers.cascade_query(hot, warm, q, qt, thr, k=1, n_probe=4,
                               tail=0)
    int8 = tiers.cascade_query(hot, warm, q, qt, thr, k=1, n_probe=4,
                               tail=0, use_kernel=True, quantized=True)
    f_hit, i_hit = np.asarray(fp32.hit), np.asarray(int8.hit)
    assert f_hit.sum() > 0
    recall = (f_hit & i_hit).sum() / max(f_hit.sum(), 1)
    assert recall >= 0.995, recall
    # hits agree on the value id too (selection, not just the flag)
    both = f_hit & i_hit
    np.testing.assert_array_equal(np.asarray(fp32.value_ids)[both],
                                  np.asarray(int8.value_ids)[both])


# ---------------------------------------------------------------------------
# sharded CacheService: publish swap mid-stream, tenant eviction
# ---------------------------------------------------------------------------

def _svc(S, **kw):
    cfg = dict(dim=16, hot_capacity=32, warm_capacity=128, n_clusters=8,
               bucket=32, n_probe=4, threshold=0.9, flush_size=8,
               rebuild_every=2, mesh=make_host_mesh(1, S))
    cfg.update(kw)
    return CacheService(**cfg)


def _insert(svc, keys, texts, tenant=0):
    return commit_insert(svc, keys, texts, tenant=tenant)


def _lookup(svc, keys, tenant=0):
    return plan_lookup(svc, keys, tenant=tenant)


@pytest.mark.parametrize("S", [2])
def test_sharded_warm_publish_swap_mid_stream(S):
    """Double-buffered rebuild on the sharded tier: lookups issued
    while the shadow builds read the old per-shard indexes at full
    recall, and the publish swaps every shard's index in one atomic
    step (no shard can be observed half-swapped)."""
    _need_devices(S)
    svc = _svc(S, background_rebuild=True, rebuild_every=3)
    gate = threading.Event()
    real = svc._rebuild
    state = {"first": True}

    def gated(warm):
        if state["first"]:
            state["first"] = False
            assert gate.wait(timeout=60), "gate never opened"
        return real(warm)

    svc._rebuild = gated
    keys = _unit(rng.standard_normal((16, 16)).astype(np.float32))
    _insert(svc, keys, [f"r{i}" for i in range(16)])
    svc.flush(rebuild=True)                    # starts the gated shadow
    assert svc.stats_snapshot().rebuild["in_flight"]
    idx_before = np.asarray(svc.warm.indexed_total).copy()

    # mid-rebuild: old index + per-shard tail windows serve everything
    hit, _, vals = _lookup(svc, keys)
    assert hit.all() and all(v is not None for v in vals)
    keys2 = _unit(rng.standard_normal((8, 16)).astype(np.float32))
    _insert(svc, keys2, [f"s{i}" for i in range(8)])
    svc.flush(rebuild=False)
    hit, _, _ = _lookup(svc, np.concatenate([keys, keys2]))
    assert hit.all()
    np.testing.assert_array_equal(np.asarray(svc.warm.indexed_total),
                                  idx_before)  # nothing published yet

    gate.set()
    rep = svc.maintenance(block=True)
    assert rep.rebuild_published
    idx_after = np.asarray(svc.warm.indexed_total)
    # shard-consistent swap: every shard's indexed_total advanced in
    # the same publish (none left behind on the old snapshot)
    assert (idx_after > idx_before).all(), (idx_before, idx_after)
    hit, _, _ = _lookup(svc, np.concatenate([keys, keys2]))
    assert hit.all()


@pytest.mark.parametrize("S", [2])
def test_evict_tenant_on_sharded_warm_tier(S):
    _need_devices(S)
    svc = _svc(S)
    all_keys = {0: [], 1: []}
    for step in range(12):
        t = step % 2
        e = _unit(rng.standard_normal((8, 16)).astype(np.float32))
        all_keys[t].append(e)
        _insert(svc, e, [f"t{t}-{step}-{i}" for i in range(8)], tenant=t)
    assert svc.stats_snapshot().tiers["demotions"] > 0   # warm populated
    live_before = len(svc.responses)
    n = svc.evict_tenant(0)
    assert n > 0 and len(svc.responses) == live_before - n
    hit, _, _ = _lookup(svc, np.concatenate(all_keys[0]), tenant=0)
    assert not hit.any()
    hit, _, vals = _lookup(svc, np.concatenate(all_keys[1]), tenant=1)
    assert hit.all() and all(v is not None for v in vals)
    # evicted ids are gone from every shard's device arrays
    valid = np.asarray(svc.warm.valid)
    tenants = np.asarray(svc.warm.tenants)
    assert not (valid & (tenants == 0)).any()


@pytest.mark.parametrize("S", [2])
@pytest.mark.parametrize("warm_dtype", ["float32", "int8"])
def test_sharded_service_serves_identically_to_unsharded(S, warm_dtype):
    """Same insert trace through an unsharded and a sharded service:
    hit decisions and served strings agree (the sharded tier holds the
    same rows, just distributed — only the IVF clustering differs, and
    full recall hides it on this workload)."""
    _need_devices(S)
    a = CacheService(dim=16, hot_capacity=32, warm_capacity=128,
                     n_clusters=8, bucket=32, n_probe=4, threshold=0.9,
                     flush_size=8, rebuild_every=2)
    b = _svc(S, warm_dtype=warm_dtype)
    ks = []
    for step in range(12):
        e = _unit(rng.standard_normal((8, 16)).astype(np.float32))
        ks.append(e)
        texts = [f"x{step}-{i}" for i in range(8)]
        _insert(a, e, texts)
        _insert(b, e, texts)
        keys = np.concatenate(ks)
        ha, _, va = _lookup(a, keys)
        hb, _, vb = _lookup(b, keys)
        np.testing.assert_array_equal(ha, hb, err_msg=f"step {step}")
        assert va == vb
    assert b.stats_snapshot().tiers["warm_shards"] == S


# ---------------------------------------------------------------------------
# merge property tests: ties + duplicate value-ids across shards.  The
# sharded cascade (and the §13 fused-ensemble merge on top of it)
# rides on these two helpers agreeing bit-for-bit, ties included —
# fuzzed with hypothesis when installed, else a deterministic grid.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False


def _fuzz(fallback_cases, *strategies):
    """``@given(*strategies)`` when hypothesis is available, else a
    parametrize over ``fallback_cases`` (tuples of the same arity)."""
    def deco(fn):
        if HAVE_HYPOTHESIS:
            return settings(max_examples=25,
                            deadline=None)(given(*strategies)(fn))

        def run(case):
            fn(*case)
        run.__name__ = fn.__name__      # not functools.wraps: pytest
        run.__doc__ = fn.__doc__        # would introspect __wrapped__
        return pytest.mark.parametrize("case", fallback_cases)(run)
    return deco


def _tied_candidates(S, Q, k, seed):
    """Shard-stacked candidates engineered for collisions: scores on a
    coarse grid (ties within and across shards) and value ids from a
    pool smaller than the candidate count (duplicates across shards)."""
    r = np.random.default_rng(seed)
    s = r.integers(0, 4, (S, Q, k)).astype(np.float32) / 2.0
    vids = r.integers(0, max(2, S * k // 2), (S, Q, k)).astype(np.int32)
    shard = np.broadcast_to(np.arange(S, dtype=np.int32)[:, None, None],
                            (S, Q, k)).copy()
    return s, vids, shard


_MERGE_CASES = [(1, 1, 1, 0), (2, 3, 2, 1), (3, 5, 3, 2), (8, 2, 4, 3),
                (4, 7, 2, 4), (5, 4, 1, 5)]
_merge_strategies = (st.integers(1, 8), st.integers(1, 8),
                     st.integers(1, 4), st.integers(0, 10**6)) \
    if HAVE_HYPOTHESIS else ()


@_fuzz(_MERGE_CASES, *_merge_strategies)
def test_merge_stacked_topk_is_stable_sort_of_shard_major_concat(
        S, Q, k, seed):
    """The oracle's winners are exactly the first k of a *stable*
    descending sort over the shard-major concat: ties resolve to the
    earliest (shard, candidate) position, never arbitrarily — the
    property that makes the collective and stacked forms comparable
    bit-for-bit at all."""
    s, vids, _ = _tied_candidates(S, Q, k, seed)
    sm, pm = merge_stacked_topk(k, jnp.asarray(s), jnp.asarray(vids))
    sm, pm = np.asarray(sm), np.asarray(pm)
    flat_s = np.moveaxis(s, 0, 1).reshape(Q, S * k)
    flat_p = np.moveaxis(vids, 0, 1).reshape(Q, S * k)
    for row in range(Q):
        order = np.argsort(-flat_s[row], kind="stable")[:k]
        np.testing.assert_array_equal(sm[row], flat_s[row][order],
                                      err_msg=f"row {row} scores")
        np.testing.assert_array_equal(pm[row], flat_p[row][order],
                                      err_msg=f"row {row} payload")
        assert (np.diff(sm[row]) <= 0).all()       # descending output


@_fuzz(_MERGE_CASES, *_merge_strategies)
def test_merge_payload_columns_stay_aligned_under_duplicate_vids(
        S, Q, k, seed):
    """With the same value id living on several shards at different
    scores, every payload column must be gathered with the *same*
    winner indices: each output (score, vid, shard) triple is a triple
    that actually co-occurred at one input position (no cross-shard
    recombination), and re-merging the merged result is the identity."""
    s, vids, shard = _tied_candidates(S, Q, k, seed)
    sm, pm_v, pm_s = merge_stacked_topk(
        k, jnp.asarray(s), jnp.asarray(vids), jnp.asarray(shard))
    sm, pm_v, pm_s = (np.asarray(x) for x in (sm, pm_v, pm_s))
    for row in range(Q):
        for c in range(k):
            sh = int(pm_s[row, c])
            assert any(s[sh, row, cc] == sm[row, c]
                       and vids[sh, row, cc] == pm_v[row, c]
                       for cc in range(k)), \
                (f"row {row} col {c}: (score {sm[row, c]}, vid "
                 f"{pm_v[row, c]}) never co-occurred on shard {sh}")
    # idempotence: the merged panel, treated as one shard, re-merges
    # to itself (top-k of an already sorted panel is a prefix copy)
    sm2, pv2, ps2 = merge_stacked_topk(
        k, jnp.asarray(sm[None]), jnp.asarray(pm_v[None]),
        jnp.asarray(pm_s[None]))
    np.testing.assert_array_equal(np.asarray(sm2), sm)
    np.testing.assert_array_equal(np.asarray(pv2), pm_v)
    np.testing.assert_array_equal(np.asarray(ps2), pm_s)


@pytest.mark.parametrize("S", [1, 2])
def test_merge_local_topk_collective_matches_oracle_under_ties(S):
    """The all-gather form picks identical winners on tie-heavy,
    duplicate-vid candidates — the exact inputs where an unstable
    merge would diverge between the distributed and oracle paths."""
    _need_devices(S)
    Q, k = 5, 3
    s, vids, shard = _tied_candidates(S, Q, k, seed=9)
    sm_o, pv_o, ps_o = merge_stacked_topk(
        k, jnp.asarray(s), jnp.asarray(vids), jnp.asarray(shard))
    mesh = make_host_mesh(1, S)
    fn = jax.shard_map(
        lambda sl, vl, hl: merge_local_topk(
            "model", k, sl.reshape(Q, k), vl.reshape(Q, k),
            hl.reshape(Q, k)),
        mesh=mesh, in_specs=(P("model"), P("model"), P("model")),
        out_specs=(P(), P(), P()), check_vma=False)
    sm_c, pv_c, ps_c = jax.jit(fn)(jnp.asarray(s), jnp.asarray(vids),
                                   jnp.asarray(shard))
    np.testing.assert_array_equal(np.asarray(sm_c), np.asarray(sm_o))
    np.testing.assert_array_equal(np.asarray(pv_c), np.asarray(pv_o))
    np.testing.assert_array_equal(np.asarray(ps_c), np.asarray(ps_o))
