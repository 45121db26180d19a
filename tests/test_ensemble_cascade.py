"""Fused multi-embedder ensemble cascade (DESIGN.md §13): kernel vs
the jnp cascade (fp32/int8, blockwise), the E=1 degenerate identity
with the single cascade, panel/base mutation alignment, sharded
shard_map-vs-oracle parity across 1/2/8 virtual devices, panel
versioning via `publish_panel`, and the service-level round trip
(plan/commit/flush alignment, mixture-weight learning through
`maintenance()`, stale-version commit rejection).  Multi-device cases
need ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
dedicated CI job); below that device count they skip."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.cache_service import CacheRequest, CacheService, FeedbackConfig
from repro.cache_service import tiers
from repro.kernels.cascade_lookup import kernel as clk_kernel
from repro.kernels.cascade_lookup import ref as clk_ref

rng = np.random.default_rng(7)

N_DEV = len(jax.devices())
E, D = 3, 16
NH, CAP, NK, BUCKET = 24, 64, 4, 20
Q = 11


def _need_devices(n):
    if N_DEV < n:
        pytest.skip(f"needs {n} devices, have {N_DEV} (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _corr_panels(n, e=E, d=D):
    """Latent-factor correlated panels, (n, E, D): the E embedders see
    the same latent with embedder-specific projections + noise."""
    z = rng.normal(size=(n, 8))
    A = rng.normal(size=(e, 8, d))
    out = np.einsum("nz,ezd->ned", z, A) + 0.3 * rng.normal(size=(n, e, d))
    return _unit(out).astype(np.float32)


def _weights(n_q, e=E):
    w = rng.uniform(0.1, 1.0, size=(n_q, e)).astype(np.float32)
    return w / w.sum(1, keepdims=True)


def _assert_same(a, b, fields=tiers.EnsembleResult._fields, msg=""):
    for name in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=f"{msg}{name}")


# ---------------------------------------------------------------------------
# kernel layer: pallas kernel vs the jnp cascade
# ---------------------------------------------------------------------------

def _kernel_fixture(e=E, n_q=9, nh=40, cap=96, n_k=6, bucket=24):
    q = _unit(rng.normal(size=(e, n_q, D))).astype(np.float32)
    w = _weights(n_q, e)
    qt = rng.integers(0, 3, n_q).astype(np.int32)
    thr = np.full(n_q, 0.3, np.float32)
    hk = _unit(rng.normal(size=(e, nh, D))).astype(np.float32)
    hv = rng.random(nh) < 0.8
    ht = rng.integers(0, 3, nh).astype(np.int32)
    hvid = np.arange(nh, dtype=np.int32)
    wk = _unit(rng.normal(size=(e, cap, D))).astype(np.float32)
    wv = rng.random(cap) < 0.85
    wt = rng.integers(0, 3, cap).astype(np.int32)
    wvid = 1000 + np.arange(cap, dtype=np.int32)
    wseq = rng.permutation(cap).astype(np.int32) + 1
    cent = _unit(rng.normal(size=(n_k, D))).astype(np.float32)
    members = np.full((n_k, bucket), -1, np.int32)
    for i, s in enumerate(rng.permutation(cap)):
        c, col = i % n_k, i // n_k
        if col < bucket:
            members[c, col] = s
    amax = np.abs(wk).max(-1)
    scales = (amax / 127.0).astype(np.float32)
    wkq = np.clip(np.round(wk / scales[..., None]), -127, 127) \
        .astype(np.int8)
    args = tuple(jnp.asarray(a) for a in (
        qt, thr, hk, hv, ht, hvid, wk, wv, wt, wvid, wseq, cent, members,
        np.int32(37), np.int32(cap - 20)))
    return jnp.asarray(q), jnp.asarray(w), args, \
        jnp.asarray(wkq), jnp.asarray(scales)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("warm_block_n", [None, 32, 17])
def test_kernel_matches_four_op_oracle(quantized, warm_block_n):
    """One pallas pass over the E stacked panels is bit-exact with the
    served jnp cascade — partial probes, tail window, invalid
    slots, mixed tenants, uneven warm blocking included."""
    q, w, args, wkq, scales = _kernel_fixture()
    ref = clk_ref.ensemble_lookup(q, w, *args, warm_keys_q=wkq,
                                  warm_scales=scales, k=3, n_probe=4,
                                  tail=12, quantized=quantized)
    ker = clk_kernel.cascade_lookup_ensemble(
        q, w, *args, warm_keys_q=wkq, warm_scales=scales, k=3, n_probe=4,
        tail=12, quantized=quantized, warm_block_n=warm_block_n,
        interpret=True)
    for name, a, b in zip(("scores", "vids", "wslots", "hslots",
                           "hot_hit", "hit"), ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_kernel_e1_degenerate_equals_single_cascade():
    """E=1 with weight 1.0 collapses to the plain cascade bit-for-bit
    (the fused score is the one cosine times 1.0)."""
    q, _, args, wkq, scales = _kernel_fixture()
    qt, thr, hk, hv, ht, hvid, wk, wv, wt, wvid, wseq, cent, members, \
        cur, idx = args
    one = jnp.ones((q.shape[1], 1), jnp.float32)
    single = clk_ref.cascade_lookup(
        q[0], qt, thr, hk[0], hv, ht, hvid, wk[0], wv, wt, wvid, wseq,
        cent, members, cur, idx, warm_keys_q=wkq[0],
        warm_scales=scales[0], k=2, n_probe=4, tail=12)
    ens = clk_ref.ensemble_lookup(
        q[:1], one, qt, thr, hk[:1], hv, ht, hvid, wk[:1], wv, wt, wvid,
        wseq, cent, members, cur, idx, warm_keys_q=wkq[:1],
        warm_scales=scales[:1], k=2, n_probe=4, tail=12)
    for name, a, b in zip(("scores", "vids", "wslots", "hslots",
                           "hot_hit", "hit"), single, ens):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# tiers layer: mutation alignment + query parity
# ---------------------------------------------------------------------------

def _tiers_fixture():
    """Populated hot+warm with aligned E-panels, via the real mirrored
    mutation path (insert batch -> demote -> append -> rebuild)."""
    hot = tiers.init_hot(NH, D)
    warm = tiers.init_warm(CAP, D, NK, BUCKET)
    ens = tiers.init_ensemble(E, hot, warm)
    n1 = 40
    embs = _corr_panels(n1)
    vids = np.arange(n1, dtype=np.int32)
    vids[5] = -1                              # one admission skip
    tens = (np.arange(n1) % 3).astype(np.int32)
    hot, ens, _ = tiers.ensemble_hot_insert_batch(
        hot, ens, jnp.asarray(embs), jnp.asarray(vids), jnp.asarray(tens))
    m = 8
    slots = tiers.coldest_slots(hot, m)
    pk = ens.hot_keys[:, slots]
    hot, dem = tiers.demote_coldest(hot, m)
    warm_pre = warm
    warm, _ = tiers.warm_append(warm, dem)
    ens = tiers.ensemble_warm_append(ens, warm_pre, dem, pk)
    return hot, tiers.warm_rebuild(warm, iters=4), ens


def test_mutations_keep_pilot_panel_bit_equal_to_base():
    """Panel 0 mirrors every slot decision of the base tiers — after
    insert/demote/append the pilot leaves are bit-equal to the base
    key panels (keys, int8 codes and scales)."""
    hot, warm, ens = _tiers_fixture()
    np.testing.assert_array_equal(np.asarray(ens.hot_keys[0]),
                                  np.asarray(hot.keys), err_msg="hot")
    # warm was rebuilt *after* the mirrored append: rebuild never
    # permutes rows, so the panels stay aligned through it
    np.testing.assert_array_equal(np.asarray(ens.warm_keys[0]),
                                  np.asarray(warm.keys), err_msg="warm")
    np.testing.assert_array_equal(np.asarray(ens.warm_keys_q[0]),
                                  np.asarray(warm.keys_q), err_msg="q8")
    np.testing.assert_array_equal(np.asarray(ens.warm_scales[0]),
                                  np.asarray(warm.scales), err_msg="sc")


@pytest.mark.parametrize("quantized", [False, True])
def test_tiers_fused_kernel_matches_oracle(quantized):
    hot, warm, ens = _tiers_fixture()
    qp = _corr_panels(Q)
    w = _weights(Q)
    qt = jnp.asarray((np.arange(Q) % 3).astype(np.int32))
    thr = jnp.full((Q,), 0.8, jnp.float32)
    ref = tiers.ensemble_cascade_query(
        hot, warm, ens, jnp.asarray(qp), jnp.asarray(w), qt, thr, k=2,
        n_probe=2, tail=8, quantized=quantized)
    ker = tiers.ensemble_cascade_query(
        hot, warm, ens, jnp.asarray(qp), jnp.asarray(w), qt, thr, k=2,
        n_probe=2, tail=8, use_kernel=True, quantized=quantized)
    _assert_same(ref, ker, msg=f"quant={quantized} ")
    # panel_scores consistency: where a candidate exists, the fused
    # top-1 equals the weighted sum of the reported per-panel cosines
    has = np.asarray(ref.value_ids[:, 0]) >= 0
    assert has.any()
    fused = np.einsum("qe,qe->q", np.asarray(ref.panel_scores), w)
    np.testing.assert_allclose(fused[has],
                               np.asarray(ref.scores[:, 0])[has],
                               rtol=0, atol=2e-6)


def test_tiers_e1_degenerate_matches_cascade_query():
    hot, warm, _ = _tiers_fixture()
    ens1 = tiers.init_ensemble(1, hot, warm)
    qp = _corr_panels(Q)
    qt = jnp.asarray((np.arange(Q) % 3).astype(np.int32))
    thr = jnp.full((Q,), 0.8, jnp.float32)
    r1 = tiers.ensemble_cascade_query(
        hot, warm, ens1, jnp.asarray(qp[:, :1]),
        jnp.ones((Q, 1), jnp.float32), qt, thr, k=2, n_probe=2, tail=8)
    rb = tiers.cascade_query(hot, warm, jnp.asarray(qp[:, 0]), qt, thr,
                             k=2, n_probe=2, tail=8)
    _assert_same(r1, rb, fields=tiers.CascadeResult._fields, msg="E=1 ")


# ---------------------------------------------------------------------------
# sharded: shard_map vs single-device oracle (1/2/8 virtual devices)
# ---------------------------------------------------------------------------

def _sharded_fixture(S):
    hot, _, ens = _tiers_fixture()
    per_warm, per_panels = [], []
    for si in range(S):
        wme = tiers.init_warm(CAP, D, NK, BUCKET)
        kp = _corr_panels(48)
        dem = tiers.Demoted(
            keys=jnp.asarray(kp[:, 0]),
            value_ids=jnp.asarray(2000 + 100 * si
                                  + np.arange(48, dtype=np.int32)),
            tenants=jnp.asarray((np.arange(48) % 3).astype(np.int32)),
            mask=jnp.ones(48, bool))
        wme, _ = tiers.warm_append(wme, dem)
        # panel rows follow the ring placement (append from cursor 0 is
        # the identity for m<=cap rows on a fresh ring); normalize per
        # 2-D slice so bits match warm_append's _unit exactly
        pw = jnp.zeros((E, CAP, D), jnp.float32)
        for e in range(E):
            pw = pw.at[e, :48].set(tiers._unit(jnp.asarray(kp[:, e])))
        per_panels.append(pw)
        per_warm.append(tiers.warm_rebuild(wme, iters=4))
    swarm = tiers.stack_warm(per_warm)
    wk_stack = jnp.stack(per_panels)                    # (S, E, cap, D)
    q8, sc = tiers.quantize_rows(wk_stack)
    ens_s = tiers.EnsembleState(hot_keys=ens.hot_keys, warm_keys=wk_stack,
                                warm_keys_q=q8, warm_scales=sc)
    np.testing.assert_array_equal(np.asarray(ens_s.warm_keys[0][0]),
                                  np.asarray(swarm.keys[0]))
    return hot, swarm, ens_s


@pytest.mark.parametrize("S", [1, 2, 8])
@pytest.mark.parametrize("quantized", [False, True])
def test_sharded_fused_matches_single_device_oracle(S, quantized):
    """The distributed schedule (shard_map + one (Q, k·S) merge over
    (vid, is_hot, slot, shard) payloads) is bit-exact with its
    single-device stacked emulation, `panel_scores` included."""
    _need_devices(S)
    from repro.launch.mesh import make_host_mesh

    hot, swarm, ens_s = _sharded_fixture(S)
    qp = jnp.asarray(_corr_panels(Q))
    w = jnp.asarray(_weights(Q))
    qt = jnp.asarray((np.arange(Q) % 3).astype(np.int32))
    thr = jnp.full((Q,), 0.8, jnp.float32)
    oracle = tiers.ensemble_cascade_query(
        hot, swarm, ens_s, qp, w, qt, thr, k=2, n_probe=2, tail=8,
        quantized=quantized)
    mesh = make_host_mesh(1, S)
    dist = jax.jit(lambda h, sw, es, qq, ww, t, th:
                   tiers.ensemble_cascade_query(
                       h, sw, es, qq, ww, t, th, k=2, n_probe=2, tail=8,
                       quantized=quantized, mesh=mesh))(
        hot, tiers.place_warm_sharded(swarm, mesh),
        tiers.place_ensemble_sharded(ens_s, mesh), qp, w, qt, thr)
    _assert_same(oracle, dist, msg=f"S={S} quant={quantized} ")


# ---------------------------------------------------------------------------
# publish_panel: per-embedder A/B swap
# ---------------------------------------------------------------------------

def test_publish_panel_swaps_only_the_target_panel():
    _, _, ens = _tiers_fixture()
    new_hot = _unit(rng.normal(size=(NH, D))).astype(np.float32)
    new_warm = _unit(rng.normal(size=(CAP, D))).astype(np.float32)
    ens2 = tiers.publish_panel(ens, 2, jnp.asarray(new_hot),
                               jnp.asarray(new_warm))
    np.testing.assert_array_equal(np.asarray(ens2.hot_keys[1]),
                                  np.asarray(ens.hot_keys[1]))
    np.testing.assert_array_equal(np.asarray(ens2.warm_keys[0]),
                                  np.asarray(ens.warm_keys[0]))
    np.testing.assert_allclose(np.asarray(ens2.hot_keys[2]),
                               _unit(new_hot), atol=1e-6)
    q8, sc = tiers.quantize_rows(ens2.warm_keys[2])
    np.testing.assert_array_equal(np.asarray(ens2.warm_keys_q[2]),
                                  np.asarray(q8))
    np.testing.assert_array_equal(np.asarray(ens2.warm_scales[2]),
                                  np.asarray(sc))


# ---------------------------------------------------------------------------
# service layer: plan/commit/flush alignment, weights, versioning
# ---------------------------------------------------------------------------

def _panels(n, noise=(0.9, 0.05, 0.9)):
    """Embedder 1 is informative; 0 and 2 are mostly noise."""
    z = _unit(rng.normal(size=(n, D)))
    out = np.stack([_unit(z + s * rng.normal(size=(n, D)))
                    for s in noise], 1)
    return out.astype(np.float32)


def _ens_svc(**kw):
    cfg = dict(dim=D, embedders=E, hot_capacity=32, warm_capacity=256,
               n_clusters=4, bucket=64, n_probe=4, threshold=0.80,
               flush_watermark=0.75, flush_size=8)
    cfg.update(kw)
    return CacheService(**cfg)


def test_service_plan_commit_flush_keep_panels_aligned():
    svc = _ens_svc()
    assert svc.capabilities().ensemble == E
    base = _panels(12)
    plan = svc.plan(CacheRequest.build(base,
                                       texts=[f"q{i}" for i in range(12)]))
    assert not plan.hit.any()
    assert plan.panel_scores is not None \
        and plan.panel_scores.shape == (12, E)
    rc = svc.commit(plan, [f"r{i}" for i in range(12)])
    assert rc.admitted == 12
    np.testing.assert_array_equal(np.asarray(svc.ens.hot_keys[0]),
                                  np.asarray(svc.hot.keys),
                                  err_msg="pilot hot panel after commit")
    plan2 = svc.plan(CacheRequest.build(base))
    assert plan2.hit.all()
    with pytest.raises(ValueError):
        svc.plan(CacheRequest.build(base[:, 0]))   # rank-2 under ensemble
    for i in range(6):
        b = _panels(8)
        p = svc.plan(CacheRequest.build(
            b, texts=[f"f{i}-{j}" for j in range(8)]))
        svc.commit(p, [f"fr{i}-{j}" for j in range(8)])
    svc.flush()
    np.testing.assert_array_equal(np.asarray(svc.ens.warm_keys[0]),
                                  np.asarray(svc.warm.keys),
                                  err_msg="pilot warm panel after flush")
    np.testing.assert_array_equal(np.asarray(svc.ens.warm_keys_q[0]),
                                  np.asarray(svc.warm.keys_q))


def test_service_learns_mixture_weights_from_feedback():
    """Only embedder 1 separates duplicates from impostors on this
    stream; the closed-form ridge refit must upweight it (and the
    refit must flow through `maintenance()` + the policy table)."""
    svc = _ens_svc(learned_admission=True,
                   feedback_config=FeedbackConfig(
                       min_samples=24, min_class=4, refit_interval=10,
                       reservoir=256, max_weight_step=0.5, seed=3))
    corp = _panels(16)
    pc = svc.plan(CacheRequest.build(corp,
                                     texts=[f"c{i}" for i in range(16)]))
    svc.commit(pc, [f"ans{i}" for i in range(16)])
    for step in range(30):
        i = step % 16
        # true duplicate whose noisy panels drag the uniform fused
        # score under the threshold; embedder 1 stays confident
        near = corp[i:i + 1].copy()
        near[:, 0] = _unit(0.4 * corp[i:i + 1, 0]
                           + rng.normal(size=(1, D)))
        near[:, 2] = _unit(0.4 * corp[i:i + 1, 2]
                           + rng.normal(size=(1, D)))
        near[:, 1] = _unit(corp[i:i + 1, 1]
                           + 0.05 * rng.normal(size=(1, D)))
        imp = corp[i:i + 1].copy()               # panels 0/2 agree
        imp[:, 1] = _unit(rng.normal(size=(1, D)))
        batch = np.concatenate([_unit(near), imp]).astype(np.float32)
        p = svc.plan(CacheRequest.build(batch,
                                        texts=[f"d{step}", f"i{step}"]))
        svc.commit(p, [f"ans{i}", f"other{step}"])
    assert svc.feedback.counters["ensemble_events"] > 0
    svc.maintenance(block=True)
    assert svc.feedback.weight_refit_log, "no weight refit attempted"
    applied = [r for r in svc.feedback.weight_refit_log if r.applied]
    assert applied, [(r.tenant, r.reason)
                     for r in svc.feedback.weight_refit_log]
    w = np.asarray(svc.policies.weights_state()[0])
    assert w[1] > 1.0 / E - 1e-6, w   # informative embedder upweighted
    snap = svc.stats_snapshot()
    assert snap.learning is not None and "ensemble_weights" in snap.learning
    assert snap.tiers["ensemble"] == E


def test_service_tenant_weight_override():
    svc = _ens_svc()
    svc.set_tenant_weights(5, [0.2, 0.6, 0.2])
    wq = svc.policies.weights_for(np.array([5, 99], np.int32), E)
    np.testing.assert_allclose(wq[0], [0.2, 0.6, 0.2], atol=1e-6)
    np.testing.assert_allclose(wq[1], np.full(E, 1.0 / E), atol=1e-6)


def test_service_publish_panel_versioning():
    """`publish_panel` is the A/B shadow-serving hook: it bumps the
    embed version, so a plan issued against the old panels is skipped
    at commit; panel-0 publish swaps the base tiers too."""
    svc = _ens_svc()
    base = _panels(12)
    p = svc.plan(CacheRequest.build(base,
                                    texts=[f"q{i}" for i in range(12)]))
    svc.commit(p, [f"r{i}" for i in range(12)])
    stale = svc.plan(CacheRequest.build(_panels(2), texts=["s0", "s1"]))
    nh = svc.hot.keys.shape[0]
    nw = svc.warm.keys.shape[0]
    svc.publish_panel(2,
                      _unit(rng.normal(size=(nh, D))).astype(np.float32),
                      _unit(rng.normal(size=(nw, D))).astype(np.float32))
    rcs = svc.commit(stale, ["x", "y"])
    assert rcs.stale_version_skipped == 2 and rcs.admitted == 0
    k0 = _unit(rng.normal(size=(nh, D))).astype(np.float32)
    w0 = _unit(rng.normal(size=(nw, D))).astype(np.float32)
    svc.publish_panel(0, k0, w0)
    np.testing.assert_array_equal(np.asarray(svc.ens.hot_keys[0]),
                                  np.asarray(svc.hot.keys),
                                  err_msg="pilot panel after panel-0 swap")


def test_service_constructor_guards():
    with pytest.raises(ValueError):
        CacheService(dim=D, embedders=E, learned_embedder=True)
    with pytest.raises(ValueError):
        CacheService(dim=D, ensemble_weights=[0.5, 0.5])
