"""Fused cascade-lookup kernel: interpret-mode parity with the four-op
cascade (exact score/index agreement across tenants, tail rows and
invalid slots), plus fused/unfused agreement through a real demotion
flush + rebuild cycle."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import commit_insert, plan_lookup

from repro.cache_service import CacheService, tiers
from repro.core import ivf as ivf_lib
from repro.kernels.cascade_lookup import kernel as cl_kernel
from repro.kernels.cascade_lookup import ops as cl_ops
from repro.kernels.cascade_lookup import ref as cl_ref

rng = np.random.default_rng(7)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _random_states(Nh=50, Nw=128, D=16, K=8, bucket=16, n_tenants=3,
                   unindexed=20):
    """Semantically arbitrary but shape-consistent tier arrays: random
    invalid slots, mixed tenants, a stale-index window of `unindexed`
    rows written after the last rebuild."""
    hk = jnp.asarray(_unit(rng.standard_normal((Nh, D)).astype(np.float32)))
    hv = jnp.asarray(rng.random(Nh) > 0.3)
    ht = jnp.asarray(rng.integers(0, n_tenants, Nh), jnp.int32)
    hvid = jnp.asarray(rng.integers(0, 1000, Nh), jnp.int32)
    hot = tiers.init_hot(Nh, D)._replace(keys=hk, valid=hv, tenants=ht,
                                         value_ids=hvid)

    wk = jnp.asarray(_unit(rng.standard_normal((Nw, D)).astype(np.float32)))
    wv = jnp.asarray(rng.random(Nw) > 0.2)
    wt = jnp.asarray(rng.integers(0, n_tenants, Nw), jnp.int32)
    wvid = jnp.asarray(rng.integers(1000, 2000, Nw), jnp.int32)
    wseq = jnp.asarray(rng.permutation(Nw) + 1, jnp.int32)
    cent = ivf_lib.kmeans(wk, wv, K, 4, 0)
    members, sizes = ivf_lib.build_lists(wk, wv, cent, bucket)
    warm = tiers.init_warm(Nw, D, K, bucket)._replace(
        keys=wk, valid=wv, tenants=wt, value_ids=wvid, write_seq=wseq,
        cursor=jnp.asarray(int(rng.integers(0, Nw)), jnp.int32),
        total=jnp.asarray(Nw, jnp.int32), centroids=cent, members=members,
        sizes=sizes, indexed_total=jnp.asarray(Nw - unindexed, jnp.int32))
    return hot, warm


def _queries(n_q, D, n_tenants=3):
    q = jnp.asarray(_unit(rng.standard_normal((n_q, D)).astype(np.float32)))
    qt = jnp.asarray(rng.integers(0, n_tenants, n_q), jnp.int32)
    thr = jnp.asarray(rng.uniform(0.2, 0.9, n_q).astype(np.float32))
    return q, qt, thr


def _flatten(hot, warm):
    return (hot.keys, hot.valid, hot.tenants, hot.value_ids,
            warm.keys, warm.valid, warm.tenants, warm.value_ids,
            warm.write_seq, warm.centroids, warm.members, warm.cursor,
            warm.indexed_total)


# ---------------------------------------------------------------------------
# array-level kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n_probe,tail,block_n", [
    (1, 2, 0, 64),      # no tail, single hot block
    (1, 4, 10, 16),     # tail window + multi-block hot stream
    (3, 4, 10, 16),     # k > 1
    (2, 8, 5, 32),      # n_probe clamped to n_clusters
])
def test_fused_kernel_matches_oracle(k, n_probe, tail, block_n):
    hot, warm = _random_states()
    q, qt, thr = _queries(9, 16)
    args = (q, qt, thr) + _flatten(hot, warm)
    ref = cl_ref.cascade_lookup(*args, k=k, n_probe=n_probe, tail=tail)
    ker = cl_kernel.cascade_lookup(*args, k=k, n_probe=n_probe, tail=tail,
                                   block_n=block_n, interpret=True)
    for name, a, b in zip(("scores", "value_ids", "warm_slots", "hot_slots",
                           "hot_hit", "hit"), ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("n_wblocks", [1, 2, 8])
@pytest.mark.parametrize("quantized", [False, True])
def test_blockwise_warm_stream_matches_oracle(n_wblocks, quantized):
    """DESIGN.md §12: the warm panel streams through the Pallas grid in
    blocks, so a warm slice larger than the single-block VMEM design
    size still runs — and every block count is bit-exact with the
    four-op oracle (whose panel is gathered whole), fp32 and int8,
    including ring wraparound of the tail window."""
    hot, warm = _random_states(Nw=256, unindexed=30)
    if quantized:
        warm = tiers.requantize(warm)
    q, qt, thr = _queries(9, 16)
    args = (q, qt, thr) + _flatten(hot, warm)
    kw = dict(k=3, n_probe=4, tail=16)
    qkw = dict(warm_keys_q=warm.keys_q, warm_scales=warm.scales,
               quantized=True) if quantized else {}
    ref = cl_ref.cascade_lookup(*args, **kw, **qkw)
    ker = cl_kernel.cascade_lookup(*args, **kw, **qkw, block_n=16,
                                   warm_block_n=256 // n_wblocks,
                                   interpret=True)
    for name, a, b in zip(("scores", "value_ids", "warm_slots", "hot_slots",
                           "hot_hit", "hit"), ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_blockwise_warm_stream_ragged_last_block():
    """Warm capacity not divisible by warm_block_n: the padded rows of
    the streamed panel must stay dead weight (no candidate can reach
    them), so results still match the oracle bit-for-bit."""
    hot, warm = _random_states(Nw=200, unindexed=25)
    q, qt, thr = _queries(7, 16)
    args = (q, qt, thr) + _flatten(hot, warm)
    ref = cl_ref.cascade_lookup(*args, k=2, n_probe=4, tail=12)
    ker = cl_kernel.cascade_lookup(*args, k=2, n_probe=4, tail=12,
                                   block_n=32, warm_block_n=64,
                                   interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cascade_query_warm_block_n_matches_default():
    """tiers-level: cascade_query(warm_block_n=...) on the kernel path
    equals the unfused four-op result."""
    hot, warm = _random_states(Nw=128)
    q, qt, thr = _queries(8, 16)
    base = tiers.cascade_query(hot, warm, q, qt, thr, k=2, n_probe=4,
                               tail=8, fused=False)
    blk = tiers.cascade_query(hot, warm, q, qt, thr, k=2, n_probe=4,
                              tail=8, fused=True, use_kernel=True,
                              warm_block_n=32)
    _assert_same_result(base, blk)


def test_fused_kernel_empty_warm_tier():
    """Fresh service: centroids are zero, every inverted list is empty —
    the kernel must mask all IVF candidates, not fabricate hits."""
    hot, _ = _random_states()
    warm = tiers.init_warm(64, 16, 4, 8)
    q, qt, thr = _queries(5, 16)
    args = (q, qt, thr) + _flatten(hot, warm)
    ref = cl_ref.cascade_lookup(*args, k=2, n_probe=4, tail=4)
    ker = cl_kernel.cascade_lookup(*args, k=2, n_probe=4, tail=4,
                                   block_n=32, interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_kernel_all_invalid_never_hits():
    hot = tiers.init_hot(32, 16)
    warm = tiers.init_warm(64, 16, 4, 8)
    q, qt, _ = _queries(4, 16)
    thr = jnp.full((4,), 0.0, jnp.float32)
    s, vids, _, _, hot_hit, hit = cl_kernel.cascade_lookup(
        q, qt, thr, *_flatten(hot, warm), k=1, n_probe=2, tail=4,
        block_n=32, interpret=True)
    assert float(jnp.max(s)) < -1e20
    assert not bool(jnp.any(hit)) and not bool(jnp.any(hot_hit))
    assert int(jnp.max(vids)) == -1


def test_ops_dispatch_paths_agree():
    """ops-level: forced kernel (interpret) and forced oracle agree."""
    hot, warm = _random_states()
    q, qt, thr = _queries(6, 16)
    args = (q, qt, thr) + _flatten(hot, warm)
    a = cl_ops.cascade_lookup(*args, k=2, n_probe=4, tail=6,
                              use_kernel=False)
    b = cl_ops.cascade_lookup(*args, k=2, n_probe=4, tail=6,
                              use_kernel=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# tiers-level: fused flag on the cascade
# ---------------------------------------------------------------------------

def _assert_same_result(a, b):
    for name in tiers.CascadeResult._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


def test_cascade_query_fused_matches_unfused_after_flush_rebuild():
    """Drive a real service through demotion flushes + an IVF rebuild
    cycle, then compare cascade_query(fused=True) — kernel forced —
    against fused=False on the resulting tier states."""
    d = 16
    svc = CacheService(dim=d, hot_capacity=32, warm_capacity=128,
                       n_clusters=4, bucket=32, n_probe=4, threshold=0.8,
                       flush_size=8, rebuild_every=2)
    for step in range(10):
        e = _unit(rng.standard_normal((8, d)).astype(np.float32))
        commit_insert(svc, e, [f"s{step}-{i}" for i in range(8)],
                      tenant=step % 3)
    st = svc.stats_snapshot()
    assert st.tiers["demotions"] > 0 and st.rebuild["rebuilds"] > 0
    # the warm ring now holds indexed rows AND a post-rebuild tail
    assert int(svc.warm.total - svc.warm.indexed_total) > 0

    q, qt, thr = _queries(16, d)
    for k, tail in [(1, svc._tail), (2, svc._tail), (1, 0)]:
        unfused = tiers.cascade_query(svc.hot, svc.warm, q, qt, thr, k=k,
                                      n_probe=4, tail=tail, fused=False)
        fused = tiers.cascade_query(svc.hot, svc.warm, q, qt, thr, k=k,
                                    n_probe=4, tail=tail, fused=True,
                                    use_kernel=True)
        _assert_same_result(unfused, fused)


def test_service_fused_flag_serves_identically():
    """Two services fed the same trace, one fused: every lookup must
    agree (hits, scores, served strings)."""
    d = 24
    mk = lambda fused: CacheService(
        dim=d, hot_capacity=16, warm_capacity=64, n_clusters=4, bucket=32,
        n_probe=4, threshold=0.85, flush_size=8, rebuild_every=1,
        fused=fused)
    a, b = mk(False), mk(True)
    assert not a.fused and b.fused
    for step in range(8):
        e = _unit(rng.standard_normal((8, d)).astype(np.float32))
        texts = [f"s{step}-{i}" for i in range(8)]
        commit_insert(a, e, texts, tenant=step % 2)
        commit_insert(b, e, texts, tenant=step % 2)
        for t in range(2):
            ha, sa, va = plan_lookup(a, e, tenant=t)
            hb, sb, vb = plan_lookup(b, e, tenant=t)
            np.testing.assert_array_equal(ha, hb)
            np.testing.assert_allclose(sa, sb)
            assert va == vb


def test_fused_flag_raises_where_the_chip_refuses_the_kernel(monkeypatch):
    """On a TPU, fused=True (or warm_block) compiles the kernel when it
    is set, and a compiler refusal raises NotImplementedError — never a
    silent four-op fallback.  Steered here by making the service and the
    kernel dispatch take their TPU branch: the kernel then lowers with
    interpret=False, which the CPU backend refuses."""
    from repro.cache_service import CacheConfig, TieringConfig
    from repro.cache_service import service as service_mod
    monkeypatch.setattr(service_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(cl_ops, "_on_tpu", lambda: True)

    def cfg(**kw):
        return CacheConfig(dim=16, tiering=TieringConfig(
            hot_capacity=32, warm_capacity=64, n_clusters=4, bucket=16,
            **kw))

    with pytest.raises(NotImplementedError, match="does not compile"):
        CacheService(cfg(fused=True))
    with pytest.raises(NotImplementedError, match="does not compile"):
        CacheService(cfg(warm_block=32))
    svc = CacheService(cfg())
    with pytest.raises(NotImplementedError, match="does not compile"):
        svc.set_fused(True)
    assert not svc.fused


def test_tail_invariant_warning_on_unsafe_config():
    """flush_size * rebuild_every > warm_capacity clamps the tail window
    and must warn instead of silently degrading the rebuild cadence."""
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        CacheService(dim=8, hot_capacity=64, warm_capacity=32,
                     n_clusters=2, bucket=16, flush_size=32,
                     rebuild_every=4)
    assert any("tail window" in str(x.message) for x in w)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        CacheService(dim=8, hot_capacity=64, warm_capacity=256,
                     n_clusters=2, bucket=16, flush_size=32,
                     rebuild_every=4)
    assert not [x for x in w if "tail window" in str(x.message)]
