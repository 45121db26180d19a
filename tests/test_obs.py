"""Serving telemetry (DESIGN.md §10): registry semantics, exporter
round-trip, span trees through the full serving pipeline, SLO health,
the batcher's maintenance accounting, and the stats() migration."""
import json
import threading

import jax
import numpy as np
import pytest

from repro.cache_service import CacheConfig, CacheService, TieringConfig
from repro.configs import get_config
from repro.core import SemanticCache
from repro.core.embedders import HashNgramEmbedder
from repro.core.trainer import EmbedderTrainer, FinetuneConfig
from repro.data import HashTokenizer
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_S, SCHEMA, HealthTracker, MetricsRegistry,
    Telemetry, Tracer, check_overhead_budget, child, read_jsonl,
    tenant_label, to_jsonl, to_prometheus, validate_lines, write_jsonl,
)
from repro.serving import CachedLLMService


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_label_separation():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("tenant",))
    c.inc(3, tenant=0)
    c.inc(2, tenant=1)
    c.labels(tenant=0).inc(5)          # handle path == kwargs path
    assert c.total(tenant=0) == 8
    assert c.total(tenant=1) == 2
    assert c.total() == 10
    assert reg.value("req_total") == 10
    assert reg.value("req_total", tenant=1) == 2
    assert reg.value("absent_total") == 0


def test_registry_registration_is_idempotent_but_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", labels=("tenant",))
    assert reg.counter("x_total", labels=("tenant",)) is a
    with pytest.raises(ValueError):    # kind mismatch
        reg.gauge("x_total", labels=("tenant",))
    with pytest.raises(ValueError):    # label-schema mismatch
        reg.counter("x_total", labels=("stage",))
    with pytest.raises(ValueError):    # typo'd label at the call site
        a.inc(1, tenannt=0)


def test_histogram_bucket_boundaries():
    """A value equal to a bound lands in that bound's bucket (`le` is
    inclusive, the Prometheus convention), strictly-greater values in
    the next; beyond the last bound is the overflow bucket."""
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "lat", buckets=(1.0, 2.0, 4.0))
    s = h.labels()
    for v in (0.5, 1.0, 1.5, 2.0, 2.5, 4.0, 9.0):
        s.observe(v)
    assert s.counts == [2, 2, 2, 1]    # le=1: {0.5,1.0}; le=2: {1.5,2.0}
    assert s.count == 7 and s.vmin == 0.5 and s.vmax == 9.0
    assert s.sum == pytest.approx(20.5)
    with pytest.raises(ValueError):    # unsorted bounds refused
        reg.histogram("bad_seconds", buckets=(2.0, 1.0))


def test_histogram_quantiles_interpolate():
    reg = MetricsRegistry()
    s = reg.histogram("q_seconds", buckets=(1.0, 2.0, 4.0)).labels()
    for v in (0.2, 0.4, 1.2, 1.8, 3.0, 8.0):
        s.observe(v)
    q50 = s.quantile(0.5)
    assert 1.0 <= q50 <= 2.0           # rank 3 lands in the (1, 2] bucket
    # overflow interpolates toward the observed max, stays finite
    assert 4.0 <= s.quantile(1.0) <= 8.0
    assert s.mean == pytest.approx(sum((0.2, 0.4, 1.2, 1.8, 3.0, 8.0)) / 6)
    # aggregate() over label subsets is a vector add of fixed buckets
    h2 = reg.histogram("stage_h_seconds", labels=("stage", "tenant"),
                       buckets=(1.0, 2.0))
    h2.observe(0.5, stage="plan", tenant="0")
    h2.observe(0.7, stage="plan", tenant="1")
    h2.observe(1.5, stage="commit", tenant="0")
    assert h2.aggregate(stage="plan").count == 2
    assert h2.aggregate(tenant="0").count == 2
    assert h2.aggregate().count == 3


def test_tenant_label():
    assert tenant_label(np.zeros(4, np.int32)) == "0"
    assert tenant_label(np.array([3, 3, 3])) == "3"
    assert tenant_label(np.array([1, 2])) == "mixed"
    assert tenant_label(np.array([], np.int32)) == "none"
    assert tenant_label(7) == "7"


def test_snapshot_under_concurrent_writer():
    """snapshot() from a drain thread while the single writer records:
    every snapshot is well-formed JSON with monotone counters (the
    torn-across-metrics-never-within-a-value contract)."""
    reg = MetricsRegistry()
    c = reg.counter("w_total").labels()
    h = reg.histogram("w_seconds", buckets=DEFAULT_LATENCY_BUCKETS_S
                      ).labels()
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            c.inc()
            h.observe(3e-3)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        last = 0
        for _ in range(100):
            snap = reg.snapshot()
            json.dumps(snap)                       # JSON-able as-is
            cur = snap["metrics"]["w_total"]["series"][0]["value"]
            assert cur >= last                     # counters never rewind
            last = cur
    finally:
        stop.set()
        t.join()
    # quiescent snapshot is internally consistent and validates clean
    snap = reg.snapshot()
    s = snap["metrics"]["w_seconds"]["series"][0]
    assert sum(s["buckets"]) == s["count"]
    assert validate_lines(to_jsonl(snap).splitlines()) == []


def test_export_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total", labels=("tenant",)).inc(4, tenant=2)
    reg.gauge("b_occupancy").set(0.75)
    reg.histogram("c_seconds", labels=("stage",),
                  buckets=(1e-3, 1.0)).observe(2e-3, stage="plan")
    path = tmp_path / "metrics.jsonl"
    write_jsonl(path, reg.snapshot(), meta={"run": "t"})
    write_jsonl(path, reg.snapshot(), meta={"run": "t"}, append=True)
    metas, series = read_jsonl(path)
    assert len(metas) == 2 and metas[0]["schema"] == SCHEMA
    assert metas[0]["run"] == "t"
    by_name = {(s["name"], tuple(sorted(s["labels"].items()))): s
               for s in series}
    assert by_name[("a_total", (("tenant", "2"),))]["value"] == 4
    assert by_name[("b_occupancy", ())]["value"] == 0.75
    hist = by_name[("c_seconds", (("stage", "plan"),))]
    assert hist["count"] == 1 and sum(hist["buckets"]) == 1
    assert validate_lines(path.read_text().splitlines()) == []
    prom = to_prometheus(reg.snapshot())
    assert '# TYPE a_total counter' in prom
    assert 'a_total{tenant="2"} 4' in prom
    assert 'c_seconds_bucket{stage="plan",le="+Inf"} 1' in prom
    assert 'c_seconds_count{stage="plan"} 1' in prom


def test_export_validate_catches_corruption():
    reg = MetricsRegistry()
    reg.counter("ok_total").inc()
    lines = to_jsonl(reg.snapshot()).splitlines()
    assert validate_lines(lines) == []
    assert validate_lines(["not json"])
    assert validate_lines(['{"kind": "counter"}'])   # no leading meta
    bad = json.loads(lines[1])
    bad["value"] = "NaN-ish"
    assert validate_lines([lines[0], json.dumps(bad)])


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ring():
    tr = Tracer(keep=2)
    with tr.span("request", tenant="0") as root:
        with tr.span("embed"):
            pass
        with tr.span("plan"):
            with tr.span("warm_probe"):
                pass
    assert tr.current() is None
    assert tr.last_root() is root
    assert root.stage_names() == ["embed", "plan"]
    assert root.find("warm_probe") is not None
    assert [s.name for s in root.walk()] == [
        "request", "embed", "plan", "warm_probe"]
    d = root.to_dict()
    assert d["name"] == "request" and len(d["children"]) == 2
    assert d["duration_s"] >= d["children"][0]["duration_s"]
    for i in range(3):                 # ring keeps the 2 most recent
        with tr.span(f"r{i}"):
            pass
    assert [s.name for s in tr.roots()] == ["r1", "r2"]
    assert [s.name for s in tr.drain()] == ["r1", "r2"]
    assert tr.roots() == []


def test_disabled_tracer_is_inert():
    tel = Telemetry.disabled()
    with tel.tracer.span("request") as s:
        assert s.duration_s == 0.0
    assert tel.tracer.last_root() is None
    tel.registry.counter("x_total").inc(5)
    assert tel.registry.value("x_total") == 0
    assert tel.health is None


@pytest.mark.parametrize("enabled,annotate", [(True, False), (True, True),
                                              (False, False)])
def test_child_span_needs_an_open_parent(enabled, annotate):
    """``child`` adds a span under the span open on this thread, in
    that span's tracer; with none open (or only a disabled tracer's) it
    returns the shared no-op context and records nothing, and it never
    creates a root."""
    tr = Tracer(enabled=enabled, annotate_xla=annotate)
    idle = child("plan.sync")
    assert idle is child("embed.sync")           # one shared no-op
    with idle as s:
        assert s.duration_s == 0.0
    assert tr.roots() == []
    with tr.span("request"):
        with child("plan.sync", rows=3):
            with child("inner"):
                pass
        seen = []
        t = threading.Thread(target=lambda: seen.append(child("off")))
        t.start()
        t.join()
        assert seen == [idle]                    # another thread's span
    assert child("plan.sync") is idle            # closed again
    if not enabled:
        assert tr.roots() == []
        return
    (root,) = tr.roots()
    assert [s.name for s in root.walk()] == ["request", "plan.sync",
                                             "inner"]
    assert root.find("plan.sync").attrs == {"rows": 3}


# ---------------------------------------------------------------------------
# spans inside the cache service and the embedder
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_embedder():
    cfg = get_config("modernbert-149m").reduced(vocab_size=512)
    tok = HashTokenizer(vocab_size=512)
    return EmbedderTrainer(cfg, FinetuneConfig(max_len=12)), tok


def _embedded_service(tiny_embedder, telemetry):
    """The served path at tiny width: the embedder, then a hot tier of
    16 that flushes 4 rows (and rebuilds the IVF) once it holds 8."""
    trainer, tok = tiny_embedder
    cache = CacheService(CacheConfig(
        dim=trainer.cfg.d_model, threshold=0.999, telemetry=telemetry,
        tiering=TieringConfig(hot_capacity=16, warm_capacity=256,
                              n_clusters=4, bucket=64, n_probe=2,
                              flush_watermark=0.5, flush_size=4,
                              kmeans_iters=2)))
    svc = CachedLLMService(trainer.make_embed_fn(tok), cache, None, tok,
                           telemetry=telemetry)
    return cache, svc


def _batch(k, n):
    return [f"question {k} number {i} about item {7 * i + k}"
            for i in range(n)]


def test_handle_spans_inside_the_embedder_and_the_cache(tiny_embedder):
    """One handle call on an empty cache: 12 admissions cross the hot
    tier's watermark, so commit flushes and rebuilds inline."""
    tel = Telemetry()
    _, svc = _embedded_service(tiny_embedder, tel)
    svc.handle(_batch(0, 12))
    root = tel.tracer.last_root()
    assert root.stage_names()[:4] == ["embed", "plan", "generate",
                                      "commit"]
    assert root.find("embed").stage_names() == ["embed.tokenize",
                                                "embed.sync"]
    assert root.find("plan").stage_names() == ["plan.sync"] * 4 + [
        "plan.coalesce"]
    commit = root.find("commit")
    names = commit.stage_names()
    assert set(names) == {"commit.sync", "flush", "rebuild"}
    assert names.count("flush") == names.count("rebuild") == 1
    assert names.index("flush") < names.index("rebuild")  # siblings
    flush, rebuild = commit.find("flush"), commit.find("rebuild")
    assert set(flush.stage_names()) == {"commit.sync"}
    assert rebuild.stage_names() == ["commit.sync"]
    for span in root.walk():
        assert sum(c.duration_s for c in span.children) <= span.duration_s


def test_embed_tokenize_span_says_the_rows_and_their_bucket(tiny_embedder):
    tel = Telemetry()
    _, svc = _embedded_service(tiny_embedder, tel)
    svc.handle(_batch(0, 12))
    embed = tel.tracer.last_root().find("embed")
    assert embed.stage_names() == ["embed.tokenize", "embed.sync"]
    assert embed.find("embed.tokenize").attrs == {"rows": 12,
                                                  "padded_to": 16}


@pytest.mark.parametrize("batch", [3, 12])
def test_flush_and_rebuild_spans_count_the_service(tiny_embedder, batch):
    tel = Telemetry()
    cache, svc = _embedded_service(tiny_embedder, tel)
    for k in range(6):
        svc.handle(_batch(k, batch))
    names = [s.name for r in tel.tracer.roots() for s in r.walk()]
    snap = cache.stats_snapshot()
    assert snap.rebuild["rebuilds"] >= 1
    assert names.count("rebuild") == snap.rebuild["rebuilds"]
    assert names.count("flush") * cache.flush_size \
        == snap.tiers["demotions"]
    assert names.count("request") == 6
    assert names.count("plan.sync") == 4 * 6
    assert names.count("embed.sync") == 6


def test_disabled_telemetry_records_no_child_span(tiny_embedder):
    tel = Telemetry.disabled()
    cache, svc = _embedded_service(tiny_embedder, tel)
    svc.handle(_batch(0, 12))
    assert cache._rebuild_total_s > 0            # it flushed and rebuilt
    assert tel.tracer.roots() == []
    assert child("plan.sync") is child("commit.sync")


@pytest.mark.parametrize("name", ["encode", "cascade_query",
                                  "demote_coldest", "warm_rebuild"])
def test_served_programs_carry_their_names(tiny_embedder, name):
    """Each program of the served path compiles as ``jit_<name>``, so a
    device trace tells the encoder, the cascade, the demotion and the
    rebuild apart."""
    trainer, _ = tiny_embedder
    cache, _ = _embedded_service(tiny_embedder, Telemetry())
    d = cache.dim
    lowered = {
        "encode": lambda: trainer._encode.lower(
            trainer.params, np.zeros((64, 12), np.int32),
            np.ones((64, 12), np.int32)),
        "cascade_query": lambda: cache._lookup.lower(
            cache.hot, cache.warm, np.zeros((2, d), np.float32),
            np.zeros(2, np.int32), np.ones(2, np.float32)),
        "demote_coldest": lambda: cache._demote.lower(cache.hot),
        "warm_rebuild": lambda: cache._rebuild.lower(cache.warm),
    }[name]()
    assert f"module @jit_{name} " in lowered.as_text()


# ---------------------------------------------------------------------------
# health / SLO budget
# ---------------------------------------------------------------------------

def test_health_rates_and_budget_burn():
    h = HealthTracker(budget_for=lambda t: 0.10)
    h.observe_plan(np.zeros(8, np.int32), np.array([1, 1, 1, 1, 0, 0, 0, 0],
                                                   bool))
    for dup in (True, True, False, False):
        h.observe_admission(0, duplicate=dup, admitted=True)
    snap = h.snapshot()
    t0 = snap["tenants"]["0"]
    assert t0["hit"]["windowed"] == pytest.approx(0.5)
    assert t0["wasted_admission"]["windowed"] == pytest.approx(0.5)
    assert t0["budget"] == pytest.approx(0.10)
    assert t0["budget_burn"] == pytest.approx(5.0)    # 0.5 / 0.1
    # rebuild overlap accounting
    h.observe_rebuild_start(plans_now=10)
    assert h.snapshot()["rebuild"]["in_overlap"]
    h.observe_rebuild_publish(plans_now=17, stall_s=2e-3)
    reb = h.snapshot()["rebuild"]
    assert reb["last_overlap_plans"] == 7 and reb["publishes"] == 1
    assert reb["stall_p99_s"] == pytest.approx(2e-3)
    # drain publishes the gauges into a registry
    reg = MetricsRegistry()
    h.drain(reg)
    assert reg.value("slo_budget_burn", tenant=0) == pytest.approx(5.0)
    assert reg.value("slo_hit_rate", tenant=0, kind="window") \
        == pytest.approx(0.5)
    assert reg.value("rebuild_overlap_plans") == 7


def test_overhead_budget_check():
    assert check_overhead_budget(1.0, 1.0) == []
    assert check_overhead_budget(1.02e-3, 1e-3) == []   # inside ratio+floor
    assert check_overhead_budget(2.0, 1.0)              # 2x: violation
    msg = check_overhead_budget(1.2e-1, 1e-1)
    assert msg and "over budget" in msg[0]


# ---------------------------------------------------------------------------
# the span tree + registry deltas through the full pipeline
# ---------------------------------------------------------------------------

def _service(fused: bool):
    tel = Telemetry()
    cache = CacheService(dim=32, hot_capacity=16, warm_capacity=256,
                         n_clusters=4, bucket=32, n_probe=2,
                         threshold=0.93, flush_watermark=0.5, flush_size=4,
                         kmeans_iters=2, seed=0, fused=fused,
                         background_rebuild=True, telemetry=tel)
    embedder = HashNgramEmbedder(dim=32)
    svc = CachedLLMService(lambda qs: embedder.embed(qs), cache, None,
                           HashTokenizer(vocab_size=512))
    return tel, cache, svc


@pytest.mark.parametrize("fused", [False, True])
def test_handle_produces_complete_span_tree(fused):
    """One request through handle() yields the full §10.2 span tree —
    embed/plan/generate/commit and, once the flush watermark trips,
    maintenance — plus tenant-labeled registry deltas, for both the
    fused and unfused cascade paths."""
    tel, cache, svc = _service(fused)
    queries = [f"distinct query number {i} about topic {i}"
               for i in range(12)]
    svc.handle(queries, tenant=3)

    root = tel.tracer.last_root()
    assert root is not None and root.name == "request"
    assert root.attrs["tenant"] == "3" and root.attrs["n"] == 12
    stages = root.stage_names()
    assert stages[:4] == ["embed", "plan", "generate", "commit"]
    # 12 admissions over a 16-slot hot tier crossed the 0.5 watermark,
    # so the receipt demanded maintenance and its span is in the tree
    assert "maintenance" in stages
    gen = root.find("generate")
    assert gen.attrs["n_leaders"] >= 1
    assert sum(c.duration_s for c in root.children) <= root.duration_s * 1.5

    reg = tel.registry
    assert reg.value("serve_requests_total", tenant=3) == 12
    hits = reg.value("serve_hits_total", tenant=3)
    misses = reg.value("serve_misses_total", tenant=3)
    assert hits + misses == 12
    assert reg.value("cache_plans_total") == 1
    assert reg.value("cache_commits_total") == 1
    assert reg.value("cache_admissions_total", tenant=3,
                     decision="admitted") >= 1
    assert reg.value("serve_maintenance_calls_total") == 1

    # the stage histogram saw each stage exactly once, tenant-labeled
    stage_h = tel.stage_histogram()
    for stage in ("embed", "plan", "generate", "commit"):
        agg = stage_h.aggregate(stage=stage)
        assert agg.count == 1, stage
        assert stage_h.aggregate(stage=stage, tenant="3").count == 1
    assert stage_h.aggregate(stage="maintenance").count >= 1

    # repeated batch: hits this time, span tree again complete
    svc.handle(queries, tenant=3)
    assert reg.value("serve_hits_total", tenant=3) > hits
    assert tel.tracer.last_root().stage_names()[:4] == [
        "embed", "plan", "generate", "commit"]


def test_flat_cache_shares_telemetry_with_engine():
    """The engine adopts the backend's bundle, so one registry sees
    both serve_* and cache_* without explicit wiring."""
    tel = Telemetry()
    cache = SemanticCache(capacity=64, dim=32, threshold=0.93,
                          telemetry=tel)
    embedder = HashNgramEmbedder(dim=32)
    svc = CachedLLMService(lambda qs: embedder.embed(qs), cache, None,
                           HashTokenizer(vocab_size=512))
    assert svc.telemetry is tel
    svc.handle(["alpha beta", "gamma delta"])
    assert tel.registry.value("serve_requests_total") == 2
    assert tel.registry.value("cache_plans_total") == 1
    root = tel.tracer.last_root()
    assert root.stage_names()[:4] == ["embed", "plan", "generate",
                                      "commit"]


# ---------------------------------------------------------------------------
# stats_snapshot schema + batcher accounting
# ---------------------------------------------------------------------------

def test_stats_snapshot_schema():
    _, cache, svc = _service(fused=False)
    svc.handle(["one query", "two query"], tenant=1)
    snap = cache.stats_snapshot()
    assert snap.schema == SCHEMA
    d = snap.to_dict()
    assert set(d) >= {"schema", "traffic", "admission", "tiers",
                      "rebuild", "health"}
    assert d["traffic"]["plans"] == 1
    assert d["admission"]["admitted"] >= 1
    assert d["health"]["tenants"]["1"]["hit"]["events"] == 2
    # v2.0: the flat stats() view is gone — the typed snapshot is the
    # only stats surface
    assert not hasattr(cache, "stats")


def test_batcher_idle_tick_accounts_exactly_once():
    """Every tick with a maintenance hook increments exactly one of
    runs/skips (the satellite regression: an idle tick must never
    count as both, or as neither)."""
    from repro.configs import get_config
    from repro.models import init_lm, split
    from repro.serving import ContinuousBatcher, Request

    cfg = get_config("phi3-mini-3.8b").reduced()
    pv, _ = split(init_lm(cfg, jax.random.PRNGKey(0)))
    b = ContinuousBatcher(cfg, pv, n_slots=2, max_len=48, prompt_len=8,
                          maintenance=lambda: "ran",
                          maintenance_max_interval=4)
    rng = np.random.default_rng(5)
    before = (b.maintenance_runs, b.maintenance_skips)
    assert before == (0, 0)
    b.tick()                                 # no work at all: idle
    assert (b.maintenance_runs, b.maintenance_skips) == (1, 0)
    assert b.last_maintenance == "ran"
    for i in range(6):
        b.submit(Request(uid=i, prompt=rng.integers(
            4, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=4))
    while b.pending or any(r is not None for r in b.slot_req):
        runs0, skips0 = b.maintenance_runs, b.maintenance_skips
        b.tick()
        assert (b.maintenance_runs - runs0) \
            + (b.maintenance_skips - skips0) == 1
    st = b.stats()
    assert st["ticks"] == b.maintenance_runs + b.maintenance_skips
    assert st["finished"] == 6
    assert st["admission_wait_p50_s"] >= 0.0
