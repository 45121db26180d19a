"""CacheService — the serving-path facade over the tiered store.

Replaces bare ``SemanticCache`` in front of the LLM engine.  The host
half owns response strings (a dict keyed by value id, garbage-collected
from the eviction reports every device op returns) and the per-tenant
policy table; the device half is `tiers`: a hot exact store, a warm IVF
ring, and a single jitted cascaded lookup.

Lifecycle of an entry:

  insert (admitted miss) -> hot tier -> [cold] demotion flush -> warm
  ring -> [ring wraps or tenant evicted] -> value id reported back ->
  host frees the response string.

The hot tier flushes its ``flush_size`` coldest rows to the warm ring
whenever occupancy crosses ``flush_watermark``; every
``rebuild_every``-th flush re-clusters the warm IVF (jittable k-means).
Between rebuilds the warm lookup scans a fixed tail window sized to
cover everything appended since the last rebuild, so recall does not
dip while the index is stale.

Serving surface (DESIGN.md §7): the typed ``CacheBackend`` lifecycle —
``plan(CacheRequest) -> CachePlan`` (read side: cascade verdicts, hit
responses, admission pre-decision, miss coalescing) then
``commit(plan, responses) -> CommitReceipt`` (write side: admissions,
demotion flush, GC, maintenance obligations).  With
``background_rebuild=True`` the warm IVF re-clusters double-buffered:
a shadow index builds on a host thread from a snapshot while lookups
keep reading the published index, and ``maintenance()`` performs the
atomic publish; the tail window covers every row appended since the
*snapshot*, so recall never dips during the overlap.  The legacy
``lookup(embs) / insert(embs, responses)`` shims and the flat
``stats()`` view were removed in v2.0 — callers use plan/commit and
``stats_snapshot()`` (README has the migration table).
"""
from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache_service import tiers
from repro.cache_service.cold import ColdTier
from repro.cache_service.feedback import (
    FeedbackAccumulator, FeedbackConfig, record_refit,
)
from repro.cache_service.policy import (
    ColdRoutingPolicy, EmbedderRefreshPolicy, PolicyTable, TenantPolicy,
)
from repro.cache_service.protocol import (
    CacheCapabilities, CachePlan, CacheRequest, CommitReceipt,
    MaintenanceReport, coalesce_misses, ungrouped_misses,
)
from repro.core.calibration import Calibration
from repro.kernels.cascade_lookup.ops import _on_tpu
from repro.obs import Telemetry
from repro.obs.registry import SCHEMA, tenant_label
from repro.obs.trace import child

# every wait for the device on the write path: commit and the GC, flush,
# rebuild and append helpers that maintenance() shares with it
_WRITE_SYNC = "commit.sync"


def _jit(fn, **static):
    """``jax.jit`` of ``fn`` with ``static`` bound, under ``fn``'s own
    name: a bare `functools.partial` compiles as ``jit__unknown``."""
    bound = partial(fn, **static)
    bound.__name__ = fn.__name__
    return jax.jit(bound)


def _fetched(x, span: str) -> np.ndarray:
    """``np.asarray(x)``: a wait for the device, timed as ``span``."""
    with child(span):
        return np.asarray(x)


@dataclass(frozen=True)
class ServiceStats:
    """Typed, schema-stable ``CacheService`` snapshot (DESIGN.md §10.1).

    Every count is read from the telemetry registry (the single source
    of truth since the registry replaced the ad-hoc counter dict); the
    grouping mirrors the metric families.  ``to_dict()`` is the wire
    form the serve launcher emits under ``--metrics-json``.
    """
    schema: str                      # repro.obs/v1
    traffic: Dict[str, int]          # plans/commits/lookup_rows/hits...
    admission: Dict[str, int]        # admitted / skipped rows
    tiers: Dict[str, object]         # occupancies, demotions, evictions
    rebuild: Dict[str, object]       # rebuild counts + wall times
    learning: Optional[Dict[str, object]]   # §9 feedback state
    health: Optional[Dict[str, object]]     # §10.3 SLO snapshot
    refresh: Optional[Dict[str, object]] = None  # §11 embedder refresh

    def to_dict(self) -> Dict[str, object]:
        return {
            "schema": self.schema, "traffic": dict(self.traffic),
            "admission": dict(self.admission), "tiers": dict(self.tiers),
            "rebuild": dict(self.rebuild),
            "learning": dict(self.learning) if self.learning else None,
            "health": dict(self.health) if self.health else None,
            "refresh": dict(self.refresh) if self.refresh else None,
        }


class CacheService:
    supports_tenants = True          # legacy sniffing hook; see DESIGN.md §7
    _kwargs_warned = False           # one-release flat-kwargs shim flag

    def __init__(self, config=None, **kwargs):
        """Build the tiered service from a ``CacheConfig``.

        ``config`` is the typed v2 surface (`cache_service/config.py`):
        top-level operating point plus grouped sub-configs — tiering,
        sharding, learning, ensemble, staleness.  The pre-v2 flat
        keyword form ``CacheService(dim=..., hot_capacity=..., ...)``
        still works for one release: it warns once per process and
        maps onto the config via ``CacheConfig.from_kwargs`` (README
        migration table).

        Feature semantics (the prose below names the legacy flat
        keywords; each lives on the sub-config given in parentheses).

        Tail invariant (``TieringConfig``; see ``tiers.warm_query``):
        rows demoted into the
        warm ring stay unindexed until the next IVF rebuild and are only
        reachable through the brute-force tail window over the last
        ``tail`` ring writes.  The window is sized
        ``tail = flush_size * rebuild_every`` so that every row
        appended between rebuilds is covered — that product therefore
        must not exceed ``warm_capacity``.  When it does, the window is
        clamped to ``warm_capacity`` and ``_do_flush`` forces rebuilds
        earlier than ``rebuild_every`` would suggest (correct, but the
        configured cadence is unattainable); a warning is emitted at
        construction instead of silently accepting the config.  In the
        sharded tier every quantity in the invariant divides by the
        shard count — each flush lands ``flush_size/shards`` rows per
        shard ring, so the window, the clamp and the warning are all
        per shard.

        ``fused=True`` routes the cascade through the fused Pallas
        lookup kernel (`kernels/cascade_lookup`) on TPU — subject to
        the kernel's VMEM budget: the warm slice must fit on-chip
        (DESIGN.md §3.1).  On CPU the flag falls back to the same
        four-op math, so it never changes results or CPU latency.  On
        a TPU the kernel is compiled when the flag is set, and a kernel
        the chip's compiler refuses raises ``NotImplementedError`` with
        the compiler's reason (today it refuses the in-kernel IVF
        gathers) — it never falls back to the four-op path silently.

        ``background_rebuild=True`` double-buffers the IVF rebuild
        (DESIGN.md §7): flushes that would have re-clustered inline
        instead start a shadow build on a host thread; lookups keep
        reading the published index and ``maintenance()`` swaps the
        finished shadow in.  A flush that would push the unindexed
        backlog past the tail window first joins the in-flight build
        (or re-clusters inline if none is running), so no row is ever
        stranded out of reach.

        ``mesh`` shards the warm tier over its ``shard_axis``
        (DESIGN.md §8): the warm ring/IVF becomes
        ``mesh.shape[shard_axis]`` independent per-shard rings
        (capacity, clusters and the tail window split per shard; flush
        batches round-robin across shards), looked up via shard_map
        with a tiny (Q, k·shards) merge collective.  The hot tier
        stays replicated.  ``warm_dtype="int8"`` scans the warm panel
        from its symmetric per-row int8 quantization (~4x less
        HBM/VMEM bandwidth) and re-scores the selected rows exactly —
        reported scores stay true fp32 cosines; only candidate
        *selection* sees the bounded quantization error.

        ``learned_admission=True`` turns the static per-tenant
        operating points into a feedback loop (DESIGN.md §9): every
        commit labels its miss rows against their stored neighbours
        (duplicate / distinct), a per-tenant reservoir accumulates the
        labeled scores, and ``maintenance()`` re-derives each tenant's
        threshold and admission margin from its own observed stream —
        under hysteresis guards (min samples, max step per refit,
        monotone false-hit budget), so the points drift with the
        workload but never thrash.  ``feedback_config`` tunes the
        guards (implies ``learned_admission``).

        ``learned_embedder=True`` closes the paper's training loop at
        serving time (DESIGN.md §11): the feedback stream also pools
        labeled *text* pairs, and ``maintenance()`` periodically runs a
        one-epoch contrastive refresh of the compact embedder
        (``embedder_trainer`` + ``embedder_tokenizer``, both required)
        on a background thread — synthetic grammar pairs backfill a
        thin reservoir — then re-embeds both tiers into shadow key
        panels and hot-swaps them exactly like the double-buffered IVF
        publish.  Every plan is stamped with the embedder version it
        embedded under; commit rejects admissions from a stale version
        instead of planting old-space keys in the new panel.  A
        candidate that fails the held-out eval gate is rolled back
        (discarded) without ever becoming visible.  ``refresh_policy``
        tunes the trigger/gate (implies ``learned_embedder``).

        ``cold_capacity > 0`` adds the host-RAM cold tier (DESIGN.md
        §12): warm-ring overwrites demote their int8 rows into it
        instead of dropping them, plan-time lookups consult it for
        below-threshold queries the router deems worth a budgeted
        host→device fetch, and ``maintenance()`` asynchronously
        promotes re-hot rows back into the warm ring.  ``cold_policy``
        tunes the router (implies a cold tier of its default capacity
        when ``cold_capacity`` is 0).  The cold tier piggybacks on the
        *unsharded* warm ring's quantized panel; combine it with
        ``mesh`` and construction raises.

        ``warm_block`` streams the warm panel through the fused kernel
        in blocks of that many rows (DESIGN.md §12), lifting the
        single-block VMEM ceiling on warm capacity; None keeps the
        whole-panel residency.  Results are bit-identical either way.
        It only means something to the fused kernel, so on a TPU it is
        checked like ``fused=True``.

        ``embedders`` turns on the fused multi-embedder ensemble
        (DESIGN.md §13): an int E (or a sequence of E embedder handles,
        retained for the caller's convenience — the service itself only
        ever sees embeddings).  Requests then carry (B, E, D)
        embeddings — one row per embedder, row 0 the *pilot* that IVF
        routing, the cold tier and the §11 machinery run on — and one
        cascade pass scores all E key panels, fusing them with
        per-tenant mixture weights (``ensemble_weights`` seeds the
        default mixture; uniform 1/E otherwise).  With
        ``learned_admission`` the weights are re-learned per tenant at
        refit time from the feedback stream, and each refit
        recalibrates the tenant's threshold against the fused score.
        A candidate embedder hot-swaps through ``publish_panel`` — the
        ensemble generalization of the §11 publish (serving panel e at
        its mixture weight IS A/B shadow serving).  ``learned_embedder``
        and ``embedders`` are mutually exclusive: the §11 refresh loop
        retrains the single pilot embedder, while ensemble candidates
        publish per panel.

        ``StalenessConfig`` (§14.2) turns on TTL eviction: admitted
        rows are stamped ``now + ttl`` (the request's per-row TTL, or
        ``default_ttl``), expired rows are masked out of every tier's
        plan-time view — hot, warm and cold, fused and unfused — and
        reaped (slots + host strings freed) on the maintenance tick.
        ``clock`` injects the time source for deterministic benches.

        ``LearningConfig.conformal`` (§14.3) floors each tenant's
        serving threshold at the split-conformal quantile of its
        recent observed negatives, so the false-hit budget holds under
        drift even while the §9 learned threshold lags or loosens.
        """
        from repro.cache_service.config import CacheConfig
        if isinstance(config, CacheConfig):
            if kwargs:
                raise TypeError(
                    f"CacheConfig construction takes no extra kwargs: "
                    f"{sorted(kwargs)}")
            cfg = config
        else:
            if config is not None:           # legacy positional dim
                kwargs.setdefault("dim", config)
            if "dim" not in kwargs:
                raise TypeError("CacheService needs a CacheConfig "
                                "(or the legacy dim=... kwargs form)")
            if not CacheService._kwargs_warned:
                CacheService._kwargs_warned = True
                warnings.warn(
                    "flat-kwargs CacheService(...) construction is "
                    "deprecated and will be removed next release; "
                    "build a CacheConfig (cache_service/config.py) — "
                    "see the README migration table",
                    DeprecationWarning, stacklevel=2)
            cfg = CacheConfig.from_kwargs(kwargs.pop("dim"), **kwargs)
        self.config = cfg
        tc, shc, lc = cfg.tiering, cfg.sharding, cfg.learning
        ec, stc = cfg.ensemble, cfg.staleness
        dim = cfg.dim
        topk, threshold = cfg.topk, cfg.threshold
        admission_margin, seed = cfg.admission_margin, cfg.seed
        telemetry = cfg.telemetry
        hot_capacity, warm_capacity = tc.hot_capacity, tc.warm_capacity
        n_clusters, bucket, n_probe = tc.n_clusters, tc.bucket, tc.n_probe
        flush_watermark, flush_size = tc.flush_watermark, tc.flush_size
        rebuild_every, kmeans_iters = tc.rebuild_every, tc.kmeans_iters
        fused, background_rebuild = tc.fused, tc.background_rebuild
        warm_dtype, warm_block = tc.warm_dtype, tc.warm_block
        cold_capacity, cold_policy = tc.cold_capacity, tc.cold_policy
        mesh, shard_axis = shc.mesh, shc.shard_axis
        learned_admission = lc.learned_admission
        feedback_config = lc.feedback
        learned_embedder = lc.learned_embedder
        embedder_trainer = lc.embedder_trainer
        embedder_tokenizer = lc.embedder_tokenizer
        refresh_policy = lc.refresh_policy
        embedders, ensemble_weights = ec.embedders, ec.weights

        sharded = mesh is not None
        shards = int(mesh.shape[shard_axis]) if sharded else 1
        if embedders is None:
            self.embedders: Optional[Tuple] = None
            n_embedders = 0
        elif isinstance(embedders, int):
            self.embedders = None
            n_embedders = embedders
        else:
            self.embedders = tuple(embedders)
            n_embedders = len(self.embedders)
        if n_embedders < 0 or n_embedders == 0 and embedders is not None:
            raise ValueError(f"embedders must name at least one "
                             f"embedder, got {embedders!r}")
        self.n_embedders = n_embedders
        if n_embedders and (learned_embedder or refresh_policy is not None
                            or embedder_trainer is not None):
            raise ValueError(
                "embedders= and learned_embedder= are mutually "
                "exclusive: the §11 refresh retrains the single pilot "
                "embedder in place; under an ensemble a candidate "
                "embedder is A/B-published per panel via "
                "publish_panel() instead (DESIGN.md §13)")
        if ensemble_weights is not None and not n_embedders:
            raise ValueError("ensemble_weights without embedders")
        if cold_policy is not None and cold_capacity <= 0:
            cold_capacity = 4 * warm_capacity
        if cold_capacity > 0 and sharded:
            raise ValueError(
                "cold_capacity > 0 requires the unsharded warm tier: "
                "demotion capture reads the single warm ring's int8 "
                "panel (DESIGN.md §12)")
        if warm_dtype not in ("float32", "int8"):
            raise ValueError(f"warm_dtype must be float32|int8, "
                             f"got {warm_dtype!r}")
        if flush_size is None:
            flush_size = max(hot_capacity // 4, 1)
        flush_size = min(flush_size, hot_capacity, warm_capacity)
        if sharded:
            if hot_capacity < shards:
                raise ValueError(
                    f"hot_capacity {hot_capacity} < {shards} shards: one "
                    "demotion flush cannot feed every warm shard")
            # flushes split round-robin over shards: keep them divisible
            flush_size = max(shards, (flush_size // shards) * shards)
            warm_capacity = -(-warm_capacity // shards) * shards
        rebuild_every = max(rebuild_every, 1)
        cap_local = warm_capacity // shards
        flush_local = flush_size // shards
        n_clusters_local = max(n_clusters // shards, 1)
        # every row appended since the last rebuild lies in this window
        # (per shard: each flush lands flush_local rows on each shard)
        if flush_local * rebuild_every > cap_local:
            warnings.warn(
                f"tail window flush_size*rebuild_every ("
                f"{flush_local}*{rebuild_every}="
                f"{flush_local * rebuild_every} per shard) exceeds the "
                f"per-shard warm capacity {cap_local}; clamping and "
                "forcing IVF rebuilds before the unindexed backlog "
                "outgrows the window (the configured rebuild cadence "
                "will not be honored)", stacklevel=2)
        tail = min(flush_local * rebuild_every, cap_local)

        self.dim = dim
        self.hot_capacity = hot_capacity
        self.warm_capacity = warm_capacity
        self.flush_size = flush_size
        self.flush_watermark = flush_watermark
        self.rebuild_every = rebuild_every
        self.topk = topk
        self.background_rebuild = bool(background_rebuild)
        self.warm_shards = shards
        self.warm_dtype = warm_dtype
        self._mesh = mesh
        self._shard_axis = shard_axis
        self._flush_local = flush_local
        self.warm_block = warm_block
        self.cold: Optional[ColdTier] = \
            ColdTier(cold_capacity, dim, policy=cold_policy) \
            if cold_capacity > 0 else None

        self.hot = tiers.init_hot(hot_capacity, dim)
        if sharded:
            self.warm = tiers.place_warm_sharded(
                tiers.init_warm_sharded(shards, cap_local, dim,
                                        n_clusters_local, bucket),
                mesh, shard_axis)
        else:
            self.warm = tiers.init_warm(warm_capacity, dim, n_clusters,
                                        bucket)
        self.policies = PolicyTable(TenantPolicy(threshold, admission_margin))
        # §13: E row-aligned key panels over the shared tiers; panel 0
        # (the pilot) duplicates the base keys, so every single-embedder
        # code path keeps reading the state it always did
        self.ens: Optional[tiers.EnsembleState] = None
        if n_embedders:
            ens = tiers.init_ensemble(n_embedders, self.hot, self.warm)
            self.ens = tiers.place_ensemble_sharded(ens, mesh, shard_axis) \
                if sharded else ens
            if ensemble_weights is not None:
                self.policies.set_default_weights(ensemble_weights)
        self.learned_admission = bool(learned_admission
                                      or feedback_config is not None)
        learned_embedder = bool(learned_embedder
                                or refresh_policy is not None)
        if learned_embedder and (embedder_trainer is None
                                 or embedder_tokenizer is None):
            raise ValueError(
                "learned_embedder=True needs embedder_trainer and "
                "embedder_tokenizer — the refresh trains the candidate "
                "and re-embeds the corpus through them (DESIGN.md §11)")
        self.trainer = embedder_trainer if learned_embedder else None
        self._embed_tok = embedder_tokenizer if learned_embedder else None
        self._refresh_policy = (refresh_policy or EmbedderRefreshPolicy()) \
            if learned_embedder else None
        # both learning loops (§9 admission, §11 embedder) share one
        # feedback accumulator: scores feed the per-tenant reservoirs,
        # texts feed the pooled pair reservoir
        self.feedback: Optional[FeedbackAccumulator] = \
            FeedbackAccumulator(feedback_config) \
            if (self.learned_admission or learned_embedder
                or lc.conformal) else None
        self.responses: Dict[int, str] = {}
        # raw query text per admitted value id (§11): re-embedding a
        # stored key under a refreshed embedder needs its original text
        self._texts: Dict[int, str] = {}
        self._next_vid = 0
        self._tail = tail
        self._n_probe = n_probe
        self._epoch = 0              # bumped by evict_tenant (plan staleness)
        self._embed_version = 0      # bumped by a published refresh (§11)
        self._pairs_at_refresh = 0   # pair-reservoir watermark (§11)
        self._recalibrated_thr: Optional[float] = None
        self._last_rebuild_s = 0.0
        self._rebuild_total_s = 0.0
        self._last_refresh_s = 0.0
        self._refresh_total_s = 0.0
        # counters live on the telemetry registry (DESIGN.md §10.1);
        # the few quantities receipts/overlap accounting need even with
        # telemetry disabled stay plain host ints
        self._n_plans = 0
        self._n_evictions = 0
        self._n_demoted_cold = 0
        # §14.2 TTL/staleness: masking only activates once any finite
        # deadline exists (default_ttl configured, or a request carried
        # one) — TTL-free services never pay the plan-time mask
        self.default_ttl = stc.default_ttl
        # deadlines live in float32 device arrays, where wall-clock
        # epoch seconds (~1.8e9) quantize to ~256s steps — coarser
        # than any sane TTL.  All internal times are therefore
        # *relative* to the clock's value at construction.
        raw_clock = stc.clock if stc.clock is not None else time.time
        t0 = float(raw_clock())
        self._clock = lambda: float(raw_clock()) - t0
        self._ttl_active = stc.default_ttl is not None
        # §14.3 conformal hit calibration (needs the feedback stream)
        self.conformal = bool(lc.conformal)
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        if self.telemetry.health is not None and self.feedback is not None:
            fb_cfg = self.feedback.config
            self.telemetry.health.set_budget_source(
                lambda t: fb_cfg.max_false_hit_rate)
        reg = self.telemetry.registry
        self._stage_h = self.telemetry.stage_histogram()
        self._c_plans = reg.counter(
            "cache_plans_total", "plan() calls").labels()
        self._c_commits = reg.counter(
            "cache_commits_total", "commit() calls").labels()
        self._c_stale = reg.counter(
            "cache_stale_commits_total",
            "commits whose plan predates an epoch bump").labels()
        self._c_rows = reg.counter(
            "cache_lookup_rows_total", "rows planned").labels()
        c_hits = reg.counter("cache_hits_total", "plan-time hits by tier",
                             labels=("tier",))
        self._c_hot_hits = c_hits.labels(tier="hot")
        self._c_warm_hits = c_hits.labels(tier="warm")
        self._c_cold_hits = c_hits.labels(tier="cold")
        self._m_admissions = reg.counter(
            "cache_admissions_total", "commit-time admission decisions",
            labels=("tenant", "decision"))
        self._c_demotions = reg.counter(
            "cache_demotions_total", "rows demoted hot -> warm").labels()
        self._c_evictions = reg.counter(
            "cache_evictions_total", "host response strings freed").labels()
        # §12 eviction split: a warm-ring overwrite either *demotes*
        # (cold tier captured the row — nothing was lost) or *drops*
        # (no cold tier — the string is freed).  With a cold tier the
        # dropped count must stay zero; the final drops of the
        # hierarchy happen on cold-ring overwrites instead.
        self._c_ev_demoted = reg.counter(
            "cache_evictions_demoted_total",
            "warm-ring overwrites captured into the cold tier").labels()
        self._c_ev_dropped = reg.counter(
            "cache_evictions_dropped_total",
            "warm-ring overwrites freed with no cold tier to catch "
            "them").labels()
        self._c_cold_evictions = reg.counter(
            "cache_cold_evictions_total",
            "cold-ring overwrites — the hierarchy's final drops"
        ).labels()
        self._c_cold_promotions = reg.counter(
            "cache_cold_promotions_total",
            "re-hot rows promoted cold -> warm by maintenance()"
        ).labels()
        self._c_cold_fetches = reg.counter(
            "cache_cold_fetches_total",
            "queries whose cold fetch the router approved").labels()
        self._c_cold_fetched_rows = reg.counter(
            "cache_cold_fetched_rows_total",
            "candidate rows shipped host -> device for the exact "
            "re-score").labels()
        self._c_cold_router_skips = reg.counter(
            "cache_cold_router_skips_total",
            "below-threshold queries whose cold fetch the router "
            "declined as not worth the transfer").labels()
        self._c_rebuilds = reg.counter(
            "cache_rebuilds_total",
            "IVF re-clusters completed (published or inline)").labels()
        self._c_shadow = reg.counter(
            "cache_shadow_rebuilds_total", "shadow builds started").labels()
        self._c_stale_ver = reg.counter(
            "cache_stale_version_commits_total",
            "admissions rejected because the plan embedded under an "
            "older embedder version than is live (§11)").labels()
        c_ref = reg.counter(
            "cache_embedder_refreshes_total",
            "embedder refresh lifecycle events (§11)",
            labels=("outcome",))
        self._c_refresh_started = c_ref.labels(outcome="started")
        self._c_refresh_published = c_ref.labels(outcome="published")
        self._c_refresh_rolled_back = c_ref.labels(outcome="rolled_back")
        self._c_ttl_stamped = reg.counter(
            "cache_ttl_stamped_total",
            "admitted rows stamped with a finite expiry (§14.2)").labels()
        self._c_expired_masked = reg.counter(
            "cache_expired_masked_total",
            "TTL-expired rows masked out of plan-time tier views "
            "(§14.2)").labels()
        self._c_expired_reaped = reg.counter(
            "cache_expired_reaped_total",
            "TTL-expired rows reaped by maintenance() across all "
            "tiers (§14.2)").labels()

        # double-buffer state: the shadow thread re-clusters a snapshot;
        # the host publishes (atomic _replace of the index leaves) from
        # _publish_shadow only — lookups always read self.warm
        self._shadow_thread: Optional[threading.Thread] = None
        self._shadow_box: Dict[str, object] = {}
        # refresh double-buffer (§11): the thread trains a candidate
        # embedder and re-embeds tier snapshots; _finish_refresh either
        # publishes (panels + params + version bump) or rolls back
        self._refresh_thread: Optional[threading.Thread] = None
        self._refresh_box: Dict[str, object] = {}

        self.set_fused(fused)
        self._insert = jax.jit(tiers.hot_insert_batch)
        self._touch = jax.jit(tiers.hot_touch)
        self._demote = _jit(tiers.demote_coldest, m=flush_size)
        if sharded:
            self._append = jax.jit(tiers.warm_append_sharded)
            self._rebuild = _jit(tiers.warm_rebuild_sharded,
                                 iters=kmeans_iters, seed=seed)
        else:
            self._append = jax.jit(tiers.warm_append)
            self._rebuild = _jit(tiers.warm_rebuild, iters=kmeans_iters,
                                 seed=seed)
        self._evict_tenant = jax.jit(tiers.evict_tenant)
        self._publish_keys = jax.jit(tiers.publish_reembedded_keys)
        self._mask_expired = jax.jit(tiers.mask_expired)
        self._reap_expired = jax.jit(tiers.reap_expired)
        if self.ens is not None:
            self._ens_insert = jax.jit(tiers.ensemble_hot_insert_batch)
            self._coldest = _jit(tiers.coldest_slots, m=flush_size)
            self._ens_append = jax.jit(
                tiers.ensemble_warm_append_sharded if sharded
                else tiers.ensemble_warm_append)
            self._ens_publish_panel = jax.jit(tiers.publish_panel,
                                              static_argnames=("e",))

    def set_fused(self, fused: bool) -> None:
        """Select the cascade execution path (four-op vs fused kernel);
        re-jits the lookup, so flipping it mid-serve costs one trace.
        On a TPU the kernel path is compiled here, and a refusal of the
        chip's compiler raises ``NotImplementedError`` (with the
        compiler's error chained)."""
        if (fused or self.warm_block) and _on_tpu():
            self._require_kernel_compiles()
        self.fused = bool(fused)
        self._lookup = _jit(
            tiers.cascade_query, k=self.topk, n_probe=self._n_probe,
            tail=self._tail, fused=self.fused,
            quantized=self.warm_dtype == "int8",
            mesh=self._mesh, axis=self._shard_axis,
            warm_block_n=self.warm_block)
        if getattr(self, "ens", None) is not None:
            self._ens_lookup = _jit(
                tiers.ensemble_cascade_query, k=self.topk,
                n_probe=self._n_probe, tail=self._tail, fused=self.fused,
                quantized=self.warm_dtype == "int8",
                mesh=self._mesh, axis=self._shard_axis,
                warm_block_n=self.warm_block)

    def _require_kernel_compiles(self) -> None:
        """Compile the fused kernel lookup for one query at this
        service's tier shapes; raise with the compiler's reason when the
        chip refuses it, instead of serving through another path."""
        lookup = partial(
            tiers.ensemble_cascade_query if self.ens is not None
            else tiers.cascade_query, k=self.topk, n_probe=self._n_probe,
            tail=self._tail, fused=True,
            quantized=self.warm_dtype == "int8", mesh=self._mesh,
            axis=self._shard_axis, warm_block_n=self.warm_block)
        q_shape = (1, self.n_embedders, self.dim) if self.ens is not None \
            else (1, self.dim)
        args = [self.hot, self.warm]
        if self.ens is not None:
            args += [self.ens, jax.ShapeDtypeStruct(q_shape, jnp.float32),
                     jax.ShapeDtypeStruct((1, self.n_embedders),
                                          jnp.float32)]
        else:
            args.append(jax.ShapeDtypeStruct(q_shape, jnp.float32))
        args += [jax.ShapeDtypeStruct((1,), jnp.int32),
                 jax.ShapeDtypeStruct((1,), jnp.float32)]
        try:
            jax.jit(lookup).lower(*args).compile()
        except Exception as e:   # Mosaic and XLA raise many types here
            raise NotImplementedError(
                "the fused cascade kernel (fused=True / warm_block) does "
                "not compile for this TPU; serve with the four-op cascade "
                f"(fused=False, warm_block=None). Compiler: "
                f"{type(e).__name__}: {e}") from e

    # ------------------------------------------------------------------
    # tenant policy surface
    # ------------------------------------------------------------------
    def set_tenant_policy(self, tenant: int, threshold: float,
                          admission_margin: float = 0.0) -> None:
        self.policies.set(tenant, TenantPolicy(threshold, admission_margin))

    def calibrate_tenant(self, tenant: int, scores, labels,
                         max_false_hit_rate: float = 0.01) -> Calibration:
        """Set this tenant's threshold from its own eval pairs under a
        false-hit budget."""
        return self.policies.calibrate(tenant, scores, labels,
                                       max_false_hit_rate)

    def set_tenant_weights(self, tenant: int, weights) -> None:
        """Pin one tenant's ensemble mixture weights (§13) — normalized
        to the simplex; learned refits may still move them later."""
        if self.ens is None:
            raise ValueError("set_tenant_weights needs embedders=")
        self.policies.set_weights(tenant, weights)

    def publish_panel(self, e: int, hot_keys, warm_keys) -> None:
        """Versioned publish of ONE embedder's key panels (DESIGN.md
        §13) — the ensemble generalization of the §11 re-embed publish.

        ``hot_keys`` is the (Nh, D) full-capacity hot panel under the
        candidate embedder, ``warm_keys`` the (Nw, D) warm panel
        ((S, Nw_local, D) stacked when sharded), built host-side
        exactly like `_finish_refresh` builds them: valid rows
        re-embedded, everything else carrying its current key.  The
        swap is atomic between lookups; per-slot metadata and the
        pilot-built IVF are untouched.  Serving panel ``e`` at mixture
        weight w IS A/B shadow serving of the candidate embedder at
        traffic share w — ramp w per tenant (or let the §9 weight
        learner earn it) to graduate the candidate.  Publishing the
        pilot (e=0) also swaps the base tiers' keys, since panel 0
        duplicates them.  The embedder version bumps either way, so
        plans embedded under the old panel set are rejected at commit
        (§11 staleness discipline).
        """
        if self.ens is None:
            raise ValueError("publish_panel needs embedders=")
        if not 0 <= int(e) < self.n_embedders:
            raise ValueError(f"panel {e} out of range "
                             f"[0, {self.n_embedders})")
        hk = jnp.asarray(hot_keys)
        wk = jnp.asarray(warm_keys)
        self.ens = self._ens_publish_panel(self.ens, int(e), hk, wk)
        if int(e) == 0:
            self.hot, self.warm = self._publish_keys(self.hot, self.warm,
                                                     hk, wk)
            if self._mesh is not None:
                self.warm = tiers.place_warm_sharded(
                    self.warm, self._mesh, self._shard_axis)
        if self._mesh is not None:
            self.ens = tiers.place_ensemble_sharded(
                self.ens, self._mesh, self._shard_axis)
        self._embed_version += 1

    # ------------------------------------------------------------------
    # CacheBackend protocol: plan / commit / maintenance / stats
    # ------------------------------------------------------------------
    def capabilities(self) -> CacheCapabilities:
        return CacheCapabilities(tenants=True, fused_lookup=True,
                                 admission=True,
                                 background_rebuild=self.background_rebuild,
                                 tiered=True,
                                 warm_sharded=self._mesh is not None,
                                 warm_dtype=self.warm_dtype,
                                 learned_admission=self.learned_admission,
                                 learned_embedder=self.trainer is not None,
                                 cold_tier=self.cold is not None,
                                 ensemble=self.n_embedders,
                                 ttl=True, conformal=self.conformal)

    def plan(self, request: CacheRequest, *,
             coalesce: bool = True) -> CachePlan:
        """Read side: one jitted cascade over both tiers, LRU touch,
        response resolution, admission pre-decision, miss coalescing
        (``coalesce=False`` skips the O(misses²) grouping when the
        caller won't use it — the legacy lookup shim does)."""
        t0 = time.perf_counter()
        qt = request.tenants
        # §14.3: the conformal floor rides every threshold resolution —
        # a tenant whose recent negatives crowd the learned threshold
        # serves strictly above them, budget held even mid-drift
        thr = self.policies.effective_thresholds(
            qt, self.feedback if self.conformal else None)
        # §14.2: expired rows are masked out of this plan's *view* of
        # the tiers (valid &= not-expired, before the jitted cascade —
        # elementwise, so fused/unfused/sharded/ensemble all inherit
        # it); the slots themselves are reclaimed by maintenance()
        now = float(self._clock()) if self._ttl_active else None
        hot_view, warm_view = self.hot, self.warm
        n_masked = 0
        if now is not None:
            hot_view, warm_view, nm = self._mask_expired(
                self.hot, self.warm, now)
            n_masked = int(_fetched(nm, "plan.sync"))
            if n_masked:
                self._c_expired_masked.inc(n_masked)
        panel_scores = None
        if self.ens is not None:
            # §13: one fused pass over all E panels; the pilot slice
            # (row 0) feeds every single-embedder consumer downstream
            # (cold routing, miss coalescing)
            emb_np = np.asarray(request.embeddings)
            if emb_np.ndim != 3 or emb_np.shape[1] != self.n_embedders:
                raise ValueError(
                    f"ensemble backend expects (B, {self.n_embedders}, D)"
                    f" embeddings, got {emb_np.shape}")
            pilot = emb_np[:, 0]
            weights = self.policies.weights_for(qt, self.n_embedders)
            res = self._ens_lookup(hot_view, warm_view, self.ens,
                                   jnp.asarray(emb_np),
                                   jnp.asarray(weights), jnp.asarray(qt),
                                   jnp.asarray(thr))
            panel_scores = _fetched(res.panel_scores, "plan.sync")
        else:
            pilot = np.asarray(request.embeddings)
            res = self._lookup(hot_view, warm_view, jnp.asarray(pilot),
                               jnp.asarray(qt), jnp.asarray(thr))
        self.hot = self._touch(self.hot, res.hot_slots, res.hot_hit)
        hit = _fetched(res.hit, "plan.sync")
        scores = _fetched(res.scores[:, 0], "plan.sync")
        vids = _fetched(res.value_ids[:, 0], "plan.sync").astype(np.int64)
        hot_hit = _fetched(res.hot_hit, "plan.sync")
        self._n_plans += 1
        self._c_plans.inc()
        self._c_rows.inc(len(hit))
        self._c_hot_hits.inc(int(hot_hit.sum()))
        self._c_warm_hits.inc(int((hit & ~hot_hit).sum()))
        if self.cold is not None and bool((~hit).any()):
            # §12 cold fallback: only the below-threshold rows are
            # offered, and the cold tier's own router decides which of
            # those justify a host->device fetch.  Verdicts merge
            # *before* the pre-decision/feedback/coalescing below, so
            # a cold hit is a hit everywhere downstream.
            tc = time.perf_counter()
            qn = np.asarray(pilot, np.float32)
            qn = qn / np.maximum(
                np.linalg.norm(qn, axis=1, keepdims=True), 1e-9)
            cf = self.cold.lookup(qn, np.asarray(qt),
                                  np.asarray(thr, np.float32), ~hit,
                                  now=now)
            self._stage_h.observe(time.perf_counter() - tc,
                                  stage="cold_fetch",
                                  tenant=tenant_label(qt))
            self._c_cold_fetches.inc(int(cf.consulted.sum()))
            self._c_cold_fetched_rows.inc(cf.fetched_rows)
            self._c_cold_router_skips.inc(cf.router_skips)
            chit = cf.consulted & (cf.scores >= np.asarray(thr, np.float32))
            if bool(chit.any()):
                self._c_cold_hits.inc(int(chit.sum()))
                hit = hit | chit
                scores = np.where(chit, cf.scores, scores)
                vids = np.where(chit, cf.value_ids, vids)
        responses = [self.responses.get(int(v)) if h else None
                     for h, v in zip(hit, vids)]
        admit = self.policies.pre_decision(qt, scores, hit)
        if self.feedback is not None:
            self.feedback.observe_plan(hit)
        if self.telemetry.health is not None:
            self.telemetry.health.observe_plan(qt, hit)
        with child("plan.coalesce"):
            leader = coalesce_misses(pilot, hit, qt, thr) \
                if coalesce else ungrouped_misses(hit)
        wall = time.perf_counter() - t0
        self._stage_h.observe(wall, stage="plan", tenant=tenant_label(qt))
        return CachePlan(
            request=request, hit=hit, scores=scores,
            value_ids=np.where(hit, vids, -1), responses=responses,
            admit=admit, miss_leader=leader,
            epoch=self._epoch,
            margins=np.asarray(thr, np.float32) - scores,
            top_value_ids=vids, plan_wall_s=wall,
            embed_version=self._embed_version,
            panel_scores=panel_scores, expired_masked=n_masked)

    def commit(self, plan: CachePlan,
               responses: Sequence[Optional[str]]) -> CommitReceipt:
        """Write side: admit planned misses (fresh value ids — a stale
        plan can never resurrect an id freed since plan time), flush if
        over the watermark, GC reported evictions."""
        t0 = time.perf_counter()
        self._c_commits.inc()
        if plan.epoch != self._epoch:
            # an evict_tenant landed between plan and commit; admission
            # stays safe because ids are fresh and strings are only
            # freed off device eviction reports
            self._c_stale.inc()
        rows = plan.miss_rows()
        admit = plan.admit[rows]
        n_stale_ver = 0
        if plan.embed_version != self._embed_version and len(rows):
            # the plan's embeddings were produced by an embedder version
            # that has since been hot-swapped away (§11): its hit
            # responses were already served consistently (scored against
            # the panel of its own version), but admitting its rows now
            # would plant old-space keys into the new-space panel and
            # silently mis-score every later neighbour.  Reject the
            # admissions outright and surface the count on the receipt.
            n_stale_ver = int(np.asarray(admit, bool).sum())
            admit = np.zeros_like(np.asarray(admit, bool))
            if n_stale_ver:
                self._c_stale_ver.inc(n_stale_ver)
        texts: List[Optional[str]] = [responses[i] for i in rows]
        for pos in np.nonzero(admit)[0]:
            if texts[pos] is None:
                raise ValueError(
                    f"admitted row {int(rows[pos])} has no response")
        if self.feedback is not None:
            self._observe_feedback(plan, rows, admit, texts)
        req_texts = plan.request.texts
        vids = np.full(len(rows), -1, np.int64)
        for pos in np.nonzero(admit)[0]:
            vids[pos] = self._next_vid
            self.responses[self._next_vid] = texts[pos]
            if req_texts is not None:
                self._texts[self._next_vid] = str(req_texts[int(rows[pos])])
            self._next_vid += 1
        n_admit = int(admit.sum())
        row_tenants = plan.request.tenants[rows]
        for tid in np.unique(row_tenants):
            m = row_tenants == tid
            n_a = int(admit[m].sum())
            if n_a:
                self._m_admissions.inc(n_a, tenant=int(tid),
                                       decision="admitted")
            if int(m.sum()) - n_a:
                self._m_admissions.inc(int(m.sum()) - n_a,
                                       tenant=int(tid), decision="skipped")
        evicted_before = self._n_evictions
        demoted_cold_before = self._n_demoted_cold
        n_ttl = 0
        if len(rows):
            # §14.2: stamp each admitted row's expiry deadline — the
            # request's per-row TTL wins, else the configured default,
            # else +inf (never expires).  The first finite deadline
            # activates plan-time masking for the service's lifetime.
            if plan.request.ttl is not None:
                ttl_rows = np.asarray(plan.request.ttl, np.float32)[rows]
            else:
                ttl_rows = np.full(
                    len(rows),
                    np.inf if self.default_ttl is None
                    else float(self.default_ttl), np.float32)
            expires = np.full(len(rows), np.inf, np.float32)
            fin = np.isfinite(ttl_rows)
            if fin.any():
                expires[fin] = np.float32(float(self._clock())) \
                    + ttl_rows[fin]
            n_ttl = int((fin & np.asarray(admit, bool)).sum())
            if n_ttl:
                self._ttl_active = True
                self._c_ttl_stamped.inc(n_ttl)
            if self.ens is not None:
                # (B, E, D) rows: the base insert takes the pilot slice,
                # the mirrored panels take the same slot (§13)
                self.hot, self.ens, evicted = self._ens_insert(
                    self.hot, self.ens,
                    jnp.asarray(plan.request.embeddings[rows]),
                    jnp.asarray(vids, dtype=jnp.int32),
                    jnp.asarray(plan.request.tenants[rows]),
                    jnp.asarray(expires))
            else:
                self.hot, evicted = self._insert(
                    self.hot, jnp.asarray(plan.request.embeddings[rows]),
                    jnp.asarray(vids, dtype=jnp.int32),
                    jnp.asarray(plan.request.tenants[rows]),
                    jnp.asarray(expires))
            self._gc(evicted)
            self._maybe_flush()
        wall = time.perf_counter() - t0
        self._stage_h.observe(wall, stage="commit",
                              tenant=tenant_label(plan.request.tenants))
        return CommitReceipt(
            admitted=n_admit, skipped=int((~admit).sum()),
            evicted=self._n_evictions - evicted_before,
            # a due policy refit or embedder refresh is a maintenance
            # obligation exactly like a due rebuild: the pipeline
            # discharges all three with one maintenance() call between
            # batches
            rebuild_due=self._rebuild_due()
            or (self.learned_admission and self.feedback is not None
                and self.feedback.refit_due())
            or self._refresh_thread is not None or self._refresh_due(),
            commit_wall_s=wall, trace_id=plan.request.trace_id,
            embed_version=self._embed_version,
            stale_version_skipped=n_stale_ver,
            ttl_stamped=n_ttl,
            demoted_cold=self._n_demoted_cold - demoted_cold_before,
            cold_maintenance_due=self.cold is not None
            and self.cold.maintenance_due)

    def maintenance(self, block: bool = False) -> MaintenanceReport:
        """Drive the double-buffered rebuild: publish a finished shadow
        index (atomic swap), start one if the backlog calls for it.
        ``block=True`` quiesces: it joins an in-flight build and never
        starts a new one, so the service returns with no rebuild
        running.  This is the idle tick (DESIGN.md §10.3): the health
        tracker drains here — per-tenant SLO gauges, occupancy and
        rebuild-overlap accounting all publish off the hot path."""
        t0 = time.perf_counter()
        published = started = False
        wall = 0.0
        if self._shadow_thread is not None and (
                block or not self._shadow_thread.is_alive()):
            wall = self._publish_shadow()
            published = True
        if (not block and self.background_rebuild
                and self._shadow_thread is None and self._tail_pressure()):
            self._start_shadow()
            started = True
        # §11 embedder refresh rides the same idle tick: publish (or
        # roll back) a finished candidate, then start one if the pair
        # reservoir says a refresh is due
        r_published = r_started = r_rolled = False
        r_wall = 0.0
        if self.trainer is not None:
            if self._refresh_thread is not None and (
                    block or not self._refresh_thread.is_alive()):
                r_wall, r_published, r_rolled = self._finish_refresh()
            if (not block and self._refresh_thread is None
                    and self._refresh_due()):
                self._start_refresh()
                r_started = True
        refits_applied = refits_checked = 0
        if self.feedback is not None and self.learned_admission:
            # online admission learning (DESIGN.md §9): republish every
            # tenant policy whose reservoir survives the hysteresis
            # guards — host-only work, cheap enough for every idle tick
            reports = self.policies.refit(self.feedback)
            refits_checked = len(reports)
            refits_applied = sum(r.applied for r in reports)
            for rep in reports:
                record_refit(self.telemetry.registry, rep)
        if self.feedback is not None and self.ens is not None:
            # §13: per-tenant mixture-weight refits ride the same idle
            # tick; an applied fit republishes the tenant's weights and
            # its fused-score-recalibrated threshold together
            wreps = self.policies.refit_weights(self.feedback,
                                                self.n_embedders)
            refits_checked += len(wreps)
            refits_applied += sum(r.applied for r in wreps)
            wc = self.telemetry.registry.counter(
                "ensemble_weight_refits_total",
                "per-tenant mixture-weight refit decisions by outcome "
                "(§13)", labels=("tenant", "outcome"))
            wg = self.telemetry.registry.gauge(
                "ensemble_weight", "published per-tenant mixture weight",
                labels=("tenant", "embedder"))
            for rep in wreps:
                wc.inc(1, tenant=rep.tenant,
                       outcome="applied" if rep.applied else rep.reason)
                if rep.applied:
                    for e, w in enumerate(rep.new_weights):
                        wg.set(float(w), tenant=rep.tenant, embedder=e)
        expired_reaped = 0
        if self._ttl_active:
            # §14.2 staleness reap: plan() only *masks* expired rows;
            # this is where their slots and host strings are reclaimed.
            # One jitted pass over both device tiers + the host cold
            # scan, all off the serving path.
            now = float(self._clock())
            self.hot, self.warm, h_ev, w_ev = self._reap_expired(
                self.hot, self.warm, now)
            expired_reaped = self._gc(h_ev) + self._gc(w_ev)
            if self.cold is not None:
                expired_reaped += self._gc(self.cold.reap_expired(now))
            if expired_reaped:
                self._c_expired_reaped.inc(expired_reaped)
        cold_promoted = 0
        cold_route_rebuilt = False
        if self.cold is not None:
            # §12 async promotion: re-hot cold rows climb back into the
            # warm ring here, never on the plan path.  The drain is
            # bounded by the policy's promote_max per tick.
            prom = self.cold.take_promotions(self.cold.policy.promote_max)
            if prom is not None:
                self._promote_into_warm(prom)
                cold_promoted = len(prom.value_ids)
                self._c_cold_promotions.inc(cold_promoted)
                if self._backlog() > self._tail:
                    # promotions are ring appends like any flush: the
                    # tail window must keep covering them
                    self._rebuild_inline()
            if self.cold._route_due():
                self.cold.rebuild_routes()
                cold_route_rebuilt = True
        reg = self.telemetry.registry
        reg.gauge("cache_hot_occupancy",
                  "hot-tier occupancy fraction").set(self.hot_occupancy)
        reg.gauge("cache_warm_occupancy",
                  "warm-ring occupancy fraction").set(self.warm_occupancy)
        reg.gauge("cache_live_responses",
                  "host response strings held").set(len(self.responses))
        reg.gauge("cache_warm_backlog_rows",
                  "rows appended since the published index (demotion "
                  "pressure vs the tail window)").set(self._backlog())
        if self.trainer is not None:
            reg.gauge("cache_embed_version",
                      "published embedder version (§11)"
                      ).set(self._embed_version)
        if self.cold is not None:
            reg.gauge("cache_cold_occupancy",
                      "cold-tier occupancy fraction"
                      ).set(self.cold.occupancy)
            reg.gauge("cache_cold_pending_promotions",
                      "re-hot cold rows queued for warm promotion"
                      ).set(self.cold.pending_promotions)
        if self.telemetry.health is not None:
            self.telemetry.health.drain(reg)
        host_wall = time.perf_counter() - t0
        self._stage_h.observe(host_wall, stage="maintenance", tenant="-")
        return MaintenanceReport(
            rebuild_started=started, rebuild_published=published,
            rebuild_in_flight=self._shadow_thread is not None,
            rebuild_wall_s=wall,
            refits_applied=refits_applied, refits_checked=refits_checked,
            wall_s=host_wall,
            refresh_started=r_started, refresh_published=r_published,
            refresh_rolled_back=r_rolled,
            refresh_in_flight=self._refresh_thread is not None,
            refresh_wall_s=r_wall, embed_version=self._embed_version,
            cold_promoted=cold_promoted,
            cold_route_rebuilt=cold_route_rebuilt,
            expired_reaped=expired_reaped)

    def stats_snapshot(self) -> ServiceStats:
        """The typed stats surface (DESIGN.md §10.1): every count read
        back from the telemetry registry.  With
        ``telemetry=Telemetry.disabled()`` the counter-derived fields
        read 0 — disabling telemetry trades the stats surface for zero
        recording cost (the bench's overhead guard measures that gap).
        """
        reg = self.telemetry.registry
        traffic = {
            "plans": int(reg.value("cache_plans_total")),
            "commits": int(reg.value("cache_commits_total")),
            "stale_commits": int(reg.value("cache_stale_commits_total")),
            "lookup_rows": int(reg.value("cache_lookup_rows_total")),
            "hot_hits": int(reg.value("cache_hits_total", tier="hot")),
            "warm_hits": int(reg.value("cache_hits_total", tier="warm")),
            "cold_hits": int(reg.value("cache_hits_total", tier="cold")),
        }
        admission = {
            "admitted": int(reg.value("cache_admissions_total",
                                      decision="admitted")),
            "skipped": int(reg.value("cache_admissions_total",
                                     decision="skipped")),
        }
        tiers_d = {
            "hot_occupancy": self.hot_occupancy,
            "warm_occupancy": self.warm_occupancy,
            "demotions": int(reg.value("cache_demotions_total")),
            "evictions": self._n_evictions,
            "evictions_demoted": int(
                reg.value("cache_evictions_demoted_total")),
            "evictions_dropped": int(
                reg.value("cache_evictions_dropped_total")),
            "live_responses": len(self.responses),
            "warm_shards": self.warm_shards,
            "warm_dtype": self.warm_dtype,
        }
        if self.ens is not None:
            tiers_d["ensemble"] = self.n_embedders
        if self.cold is not None:
            tiers_d["cold"] = self.cold.stats()
        if self._ttl_active:
            tiers_d["staleness"] = {
                "default_ttl": self.default_ttl,
                "ttl_stamped": int(
                    reg.value("cache_ttl_stamped_total")),
                "expired_masked": int(
                    reg.value("cache_expired_masked_total")),
                "expired_reaped": int(
                    reg.value("cache_expired_reaped_total")),
            }
        rebuild = {
            "rebuilds": int(reg.value("cache_rebuilds_total")),
            "shadow_started": int(
                reg.value("cache_shadow_rebuilds_total")),
            "in_flight": self._shadow_thread is not None,
            "last_wall_s": self._last_rebuild_s,
            "total_wall_s": self._rebuild_total_s,
        }
        learning = None
        if self.feedback is not None:
            learning = dict(self.feedback.state())
            learning["learned_policies"] = self.policies.learned_state()
            if self.ens is not None:
                learning["ensemble_weights"] = self.policies.weights_state()
            if self.conformal:
                learning["conformal"] = self.feedback.conformal_state()
        refresh = None
        if self.trainer is not None:
            refresh = {
                "embed_version": self._embed_version,
                "refreshes_started": int(reg.value(
                    "cache_embedder_refreshes_total", outcome="started")),
                "refreshes_published": int(reg.value(
                    "cache_embedder_refreshes_total", outcome="published")),
                "refreshes_rolled_back": int(reg.value(
                    "cache_embedder_refreshes_total",
                    outcome="rolled_back")),
                "stale_version_commits": int(reg.value(
                    "cache_stale_version_commits_total")),
                "refresh_in_flight": self._refresh_thread is not None,
                "last_refresh_s": self._last_refresh_s,
                "refresh_total_s": self._refresh_total_s,
                "pairs_held": len(self.feedback.pairs),
                "recalibrated_threshold": self._recalibrated_thr,
            }
        health = self.telemetry.health.snapshot() \
            if self.telemetry.health is not None else None
        return ServiceStats(schema=SCHEMA, traffic=traffic,
                            admission=admission, tiers=tiers_d,
                            rebuild=rebuild, learning=learning,
                            health=health, refresh=refresh)

    def evict_tenant(self, tenant: int) -> int:
        """Drop every entry of one tenant from both tiers; frees the
        host strings.  Returns the number of entries evicted."""
        self._epoch += 1
        self.hot, self.warm, h_ev, w_ev = self._evict_tenant(
            self.hot, self.warm, jnp.asarray(tenant, jnp.int32))
        n = self._gc(h_ev) + self._gc(w_ev)
        if self.cold is not None:
            # also purges the tenant's queued promotions: an evicted
            # tenant must not resurrect through the async drain (§12)
            n += self._gc(self.cold.evict_tenant(int(tenant)))
        return n

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _observe_feedback(self, plan: CachePlan, rows: np.ndarray,
                          admit: np.ndarray,
                          texts: List[Optional[str]]) -> None:
        """Label each committed miss against its stored neighbour and
        feed the per-tenant reservoir (DESIGN.md §9): duplicate <=> the
        generated response equals the best same-tenant neighbour's
        stored response (the plan carried its id).  A row with no
        same-tenant candidate is a definite non-duplicate; a row whose
        neighbour string was GC'd between plan and commit is
        unknowable and skipped rather than mislabeled.  Runs before
        commit mints fresh ids, so neighbour lookups only ever see
        plan-era entries.  All event/wasted-admission accounting lives
        on the accumulator (surfaced through ``stats()``)."""
        top = plan.top_value_ids
        if top is None:
            return
        tenants = plan.request.tenants
        req_texts = plan.request.texts
        if req_texts is not None and self.trainer is not None:
            # hit rows: the query cleared its tenant's threshold against
            # the stored neighbour — a served duplicate, and the
            # strongest positive contrastive pair the §11 pool sees
            for row in np.nonzero(np.asarray(plan.hit, bool))[0]:
                neigh = self._texts.get(int(plan.value_ids[row]))
                if neigh is not None:
                    self.feedback.observe_hit_pair(req_texts[int(row)],
                                                   neigh)
        for pos, row in enumerate(rows):
            text = texts[pos]
            if text is None:
                continue
            vid = int(top[row])
            if vid < 0:
                dup = False
                score = max(float(plan.scores[row]), -1.0)  # NEG sentinel
                neigh_text = None
            else:
                neighbour = self.responses.get(vid)
                if neighbour is None:
                    continue
                dup = text == neighbour
                score = float(plan.scores[row])
                # the §11 contrastive pair is (query, neighbour *query*)
                # — the texts whose embeddings the score was computed
                # between; missing when the neighbour predates text
                # retention (legacy insert path)
                neigh_text = self._texts.get(vid)
            q_text = None if req_texts is None else req_texts[int(row)]
            self.feedback.observe(int(tenants[row]), score, dup,
                                  bool(admit[pos]), text=q_text,
                                  neighbour_text=neigh_text)
            if self.ens is not None and plan.panel_scores is not None \
                    and vid >= 0:
                # §13: the same verdict, labeled with the candidate's
                # unweighted per-embedder cosines — the mixture-weight
                # learner's training event
                self.feedback.observe_ensemble(
                    int(tenants[row]), plan.panel_scores[row], dup)
            if self.telemetry.health is not None:
                self.telemetry.health.observe_admission(
                    int(tenants[row]), dup, bool(admit[pos]))

    def _gc(self, evicted) -> int:
        """Free response strings whose ids a device op reported evicted."""
        ids = _fetched(evicted, _WRITE_SYNC)
        n = 0
        for v in ids[ids >= 0]:
            self._texts.pop(int(v), None)
            if self.responses.pop(int(v), None) is not None:
                n += 1
        self._n_evictions += n
        self._c_evictions.inc(n)
        return n

    def _backlog(self) -> int:
        """Rows appended since the *published* index was built (the
        worst shard's backlog in the sharded tier — each shard has its
        own ring, so the window must cover the deepest one)."""
        return int(np.max(_fetched(self.warm.total - self.warm.indexed_total,
                                   _WRITE_SYNC)))

    def _tail_pressure(self) -> bool:
        """One more flush would push the unindexed backlog past the
        tail window — the single rebuild-trigger predicate shared by
        inline flushes, background starts and maintenance()."""
        return self._backlog() + self._flush_local > self._tail

    def _rebuild_due(self) -> bool:
        """A maintenance() call now would publish or start a rebuild."""
        if self._shadow_thread is not None:
            return True
        return self.background_rebuild and self._tail_pressure()

    # ------------------------------------------------------------------
    # §11: online embedder refresh (train -> gate -> re-embed -> publish)
    # ------------------------------------------------------------------
    def _refresh_due(self) -> bool:
        """The pair reservoir justifies a refresh attempt: enough pooled
        pairs of both labels, and enough *new* pair events since the
        last attempt (the §9 hysteresis discipline, applied to
        training runs).  With a ``synth_domain`` configured the
        class-balance guard is waived — a skewed pool (e.g. a stream
        where every observed neighbour really was a duplicate) is
        exactly what the synthetic backfill balances."""
        if self.trainer is None or self._refresh_thread is not None \
                or self.feedback is None:
            return False
        pol = self._refresh_policy
        pairs = self.feedback.pairs
        if len(pairs) < pol.min_pairs:
            return False
        if pol.synth_domain is None and (pairs.n_pos < pol.min_class
                                         or pairs.n_neg < pol.min_class):
            return False
        return self._pairs_at_refresh == 0 \
            or pairs.seen - self._pairs_at_refresh >= pol.refresh_interval

    def _start_refresh(self) -> None:
        """Kick off the refresh on a host thread: one-epoch contrastive
        fit of a *candidate* trainer (the paper's recipe — the live
        params are copied, never touched), eval gate against the frozen
        embedder on the held-out reservoir slice, then re-embed of a
        snapshot of both tiers' texts.  Everything the thread reads is
        snapshotted here; everything it produces lands in the box for
        ``_finish_refresh`` to publish or discard."""
        from repro.core.trainer import EmbedderTrainer
        pol = self._refresh_policy
        self._pairs_at_refresh = self.feedback.pairs.seen
        train_ds, eval_ds = self.feedback.pairs.split(pol.eval_frac,
                                                      seed=pol.seed)
        if pol.synth_domain is not None and (
                len(train_ds.labels) < pol.synth_min_pairs
                or _single_class(train_ds) or _single_class(eval_ds)):
            train_ds, eval_ds = _synth_backfill(train_ds, eval_ds, pol)
        snap_hot, snap_warm = self.hot, self.warm   # immutable pytrees
        snap_texts = dict(self._texts)
        baseline, tok = self.trainer, self._embed_tok
        self._refresh_box = box = {}

        def run() -> None:
            t0 = time.perf_counter()
            try:
                cand = EmbedderTrainer(baseline.cfg, baseline.ft,
                                       params=baseline.params)
                box["fit"] = cand.fit(train_ds, tok)
                gate = _eval_gate(cand, baseline, eval_ds, tok, pol)
                box["gate"] = gate
                if gate["pass"]:
                    box["trainer"] = cand
                    box["embeddings"] = _reembed_snapshot(
                        cand, tok, snap_hot, snap_warm, snap_texts)
            except BaseException as e:      # surfaced at publish time
                box["error"] = e
            box["wall"] = time.perf_counter() - t0

        self._refresh_thread = threading.Thread(
            target=run, name="embedder-refresh", daemon=True)
        self._refresh_thread.start()
        self._c_refresh_started.inc()

    def _finish_refresh(self) -> Tuple[float, bool, bool]:
        """Join the refresh thread; publish or roll back.

        Publish is the §7.1 discipline replayed against the embedder:
        the shadow re-embeddings are grafted onto the *current* tiers
        by value id (a row admitted while the thread ran is re-embedded
        inline here, so the published panel is single-space; a row
        evicted meanwhile simply has no key to graft — ``valid`` never
        moves, so nothing resurrects), the panels swap atomically
        between lookups, the live trainer adopts the candidate's params
        (the serving embed closure reads them per call — that
        assignment IS the hot swap), and the version bumps so in-flight
        plans are rejected at commit instead of mis-scored.  Rollback
        is nothing but discarding the candidate: its params were never
        visible anywhere.  Returns (wall_s, published, rolled_back).
        """
        assert self._refresh_thread is not None
        self._refresh_thread.join()
        self._refresh_thread = None
        box, self._refresh_box = self._refresh_box, {}
        err = box.get("error")
        if err is not None:
            raise RuntimeError("background embedder refresh failed") from err
        wall = float(box.get("wall", 0.0))
        self._last_refresh_s = wall
        gate = box.get("gate", {"pass": False})
        reg = self.telemetry.registry
        g = reg.gauge(
            "cache_refresh_eval",
            "last refresh's eval-gate metrics on the held-out slice "
            "(candidate vs the then-frozen baseline)",
            labels=("embedder", "metric"))
        for side in ("candidate", "baseline"):
            for k, v in (gate.get(side) or {}).items():
                if k in ("precision", "recall", "f1"):
                    g.set(float(v), embedder=side, metric=k)
        if not gate.get("pass"):
            self._c_refresh_rolled_back.inc()
            return wall, False, True
        emb: Dict[int, np.ndarray] = box["embeddings"]
        cand = box["trainer"]
        # rows admitted while the refresh ran: re-embed inline with the
        # candidate so the published panel is single-space (the §7.1
        # tail-window analogue — the snapshot covers the bulk, the
        # publish covers the delta)
        delta = [(int(v), self._texts[int(v)]) for v in self._live_vids()
                 if int(v) not in emb and int(v) in self._texts]
        if delta:
            de = cand.embed_texts([t for _, t in delta], self._embed_tok)
            emb.update({v: de[i] for i, (v, _) in enumerate(delta)})
        hot_keys = np.asarray(self.hot.keys).copy()
        hvids = np.asarray(self.hot.value_ids)
        for i in np.nonzero(np.asarray(self.hot.valid))[0]:
            e = emb.get(int(hvids[i]))
            if e is not None:
                hot_keys[i] = e
        warm_keys = np.asarray(self.warm.keys).copy()
        wvids = np.asarray(self.warm.value_ids)
        for idx in np.argwhere(np.asarray(self.warm.valid)):
            e = emb.get(int(wvids[tuple(idx)]))
            if e is not None:
                warm_keys[tuple(idx)] = e
        self.hot, self.warm = self._publish_keys(
            self.hot, self.warm, jnp.asarray(hot_keys),
            jnp.asarray(warm_keys))
        if self._mesh is not None:
            self.warm = tiers.place_warm_sharded(self.warm, self._mesh,
                                                 self._shard_axis)
        self.trainer.params = cand.params
        self.trainer.opt_state = cand.opt_state
        self._embed_version += 1
        if self._refresh_policy.recalibrate:
            # a threshold is only meaningful against one embedder's
            # score distribution: remap every tenant to the published
            # candidate's best-F1 operating point on the gate slice,
            # and drop the §9 score reservoirs (their samples live in
            # the old version's score space)
            lo, hi = self._refresh_policy.recalibrate_bounds
            new_thr = float(np.clip(
                gate["candidate"]["f1_threshold"], lo, hi))
            self.policies.recalibrate_all(new_thr)
            if self.feedback is not None:
                self.feedback.reset_scores()
            self._recalibrated_thr = new_thr
            reg.gauge(
                "cache_refresh_recalibrated_threshold",
                "serving threshold adopted at the last embedder "
                "publish (the candidate's held-out best-F1 operating "
                "point, clipped to the policy's recalibrate_bounds)"
            ).set(new_thr)
        self._refresh_total_s += wall
        self._c_refresh_published.inc()
        return wall, True, False

    def _live_vids(self) -> np.ndarray:
        """Value ids currently valid in either tier (host view)."""
        h = np.asarray(self.hot.value_ids)[np.asarray(self.hot.valid)]
        w = np.asarray(self.warm.value_ids)[np.asarray(self.warm.valid)]
        return np.unique(np.concatenate([h.ravel(), w.ravel()]))

    def _start_shadow(self) -> None:
        """Kick off a shadow re-cluster of a snapshot of the warm tier.
        The snapshot is an immutable pytree, so serving mutations keep
        building fresh states while the thread reads the old one."""
        snapshot = self.warm
        self._shadow_box = box = {}
        rebuild = self._rebuild

        def run() -> None:
            t0 = time.perf_counter()
            try:
                box["warm"] = jax.block_until_ready(rebuild(snapshot))
            except BaseException as e:          # surfaced at publish time
                box["error"] = e
            # stamped in-thread: the build itself, not the idle wait
            # for the next maintenance() tick to publish it
            box["wall"] = time.perf_counter() - t0

        self._shadow_thread = threading.Thread(
            target=run, name="warm-ivf-rebuild", daemon=True)
        self._shadow_thread.start()
        self._c_shadow.inc()
        if self.telemetry.health is not None:
            # overlap accounting (§10.3): plans served between here and
            # the publish ran against the pre-snapshot index
            self.telemetry.health.observe_rebuild_start(self._n_plans)

    def _publish_shadow(self) -> float:
        """Join the shadow thread and atomically swap its index in.

        ``indexed_total`` becomes the snapshot's total, so every row
        appended *after* the snapshot stays covered by the tail window
        — recall never dips across the swap (`tiers.warm_query`'s
        epoch partition keeps slots overwritten post-snapshot out of
        the stale inverted lists).
        """
        assert self._shadow_thread is not None
        t0 = time.perf_counter()
        with child(_WRITE_SYNC):
            self._shadow_thread.join()
        self._shadow_thread = None
        err = self._shadow_box.get("error")
        if err is not None:
            raise RuntimeError("background IVF rebuild failed") from err
        shadow = self._shadow_box["warm"]
        self.warm = tiers.warm_publish_index(self.warm, shadow)
        # the stall the serve loop actually felt: join wait + swap —
        # near zero when the build finished before the idle tick
        stall = time.perf_counter() - t0
        wall = float(self._shadow_box["wall"])
        self._last_rebuild_s = wall
        self._rebuild_total_s += wall
        self._c_rebuilds.inc()
        if self.telemetry.health is not None:
            self.telemetry.health.observe_rebuild_publish(
                self._n_plans, stall)
        return wall

    def _rebuild_inline(self) -> None:
        with child("rebuild"):
            t0 = time.perf_counter()
            warm = self._rebuild(self.warm)
            with child(_WRITE_SYNC):
                self.warm = jax.block_until_ready(warm)
        self._last_rebuild_s = time.perf_counter() - t0
        self._rebuild_total_s += self._last_rebuild_s
        self._c_rebuilds.inc()

    def _capture_and_append(self, dem: tiers.Demoted,
                            panel_keys=None) -> None:
        """Land a batch on the warm ring; route its overwrites.

        Without a cold tier a ring overwrite is the end of the line:
        GC the reported value ids and count them dropped.  With one,
        the rows about to be overwritten demote instead (§12): their
        ring positions are recomputed host-side from the pre-append
        cursor (the same arithmetic as `tiers.warm_append`, sound
        because `demote_coldest` keeps ``mask`` a True-prefix), their
        int8 panel rows are captured into the cold ring *before* the
        jitted append lands, and only the cold ring's own overwrites —
        the hierarchy's final drops — are GC'd.

        Under an ensemble (§13) ``panel_keys`` carries the batch's
        (E, m, D) stacked panel rows; the mirrored append replays the
        base ring arithmetic from the pre-append state, so the panels
        stay row-aligned.  ``None`` (the cold-promotion path, which
        only retains pilot keys) backfills every panel with the pilot
        row — exact for the pilot, a well-formed stand-in for the rest
        until the row is re-admitted.
        """
        warm_pre = self.warm
        if self.ens is not None and panel_keys is None:
            panel_keys = jnp.broadcast_to(
                dem.keys[None], (self.n_embedders,) + dem.keys.shape)
        if self.cold is None:
            self.warm, evicted = self._append(self.warm, dem)
            if self.ens is not None:
                self.ens = self._ens_append(self.ens, warm_pre, dem,
                                            panel_keys)
            self._c_ev_dropped.inc(self._gc(evicted))
            return
        n = int(_fetched(dem.mask, _WRITE_SYNC).sum())
        if n:
            cap = self.warm.keys.shape[0]
            pos = (int(_fetched(self.warm.cursor, _WRITE_SYNC))
                   + np.arange(n)) % cap
            pos = pos[_fetched(self.warm.valid, _WRITE_SYNC)[pos]]
            if len(pos):
                w = self.warm
                dropped = self.cold.insert(
                    _fetched(w.keys_q, _WRITE_SYNC)[pos],
                    _fetched(w.scales, _WRITE_SYNC)[pos],
                    _fetched(w.value_ids, _WRITE_SYNC)[pos].astype(np.int64),
                    _fetched(w.tenants, _WRITE_SYNC)[pos],
                    expires=_fetched(w.expires_at, _WRITE_SYNC)[pos])
                self._c_ev_demoted.inc(len(pos))
                self._n_demoted_cold += len(pos)
                self._c_cold_evictions.inc(self._gc(dropped))
        # the append's own eviction report covers exactly the captured
        # rows — their strings stay alive behind the cold copies
        self.warm, _ = self._append(self.warm, dem)
        if self.ens is not None:
            self.ens = self._ens_append(self.ens, warm_pre, dem,
                                        panel_keys)

    def _promote_into_warm(self, prom) -> None:
        """Append a drained cold `Promotion` to the warm ring through
        the same jitted ``flush_size``-shaped path as a demotion flush
        (chunks pad with masked rows, so no new shape is traced).
        Ring rows a promotion overwrites demote straight back into the
        cold tier — promotion must never become a covert drop path."""
        m = self.flush_size
        for lo in range(0, len(prom.value_ids), m):
            keys = np.asarray(prom.keys[lo:lo + m], np.float32)
            v = np.asarray(prom.value_ids[lo:lo + m], np.int32)
            t = np.asarray(prom.tenants[lo:lo + m], np.int32)
            x = np.asarray(prom.expires[lo:lo + m], np.float32)
            pad = m - len(v)
            dem = tiers.Demoted(
                keys=jnp.asarray(np.concatenate(
                    [keys, np.zeros((pad, self.dim), np.float32)])),
                value_ids=jnp.asarray(np.concatenate(
                    [v, np.full(pad, -1, np.int32)])),
                tenants=jnp.asarray(np.concatenate(
                    [t, np.full(pad, -1, np.int32)])),
                mask=jnp.asarray(np.concatenate(
                    [np.ones(len(v), bool), np.zeros(pad, bool)])),
                expires=jnp.asarray(np.concatenate(
                    [x, np.full(pad, np.inf, np.float32)])))
            self._capture_and_append(dem)

    def _do_flush(self, rebuild: bool) -> None:
        with child("flush"):
            pk = None
            if self.ens is not None:
                # gather the demoting rows' stacked panel keys before the
                # demote flips their valid bits — `coldest_slots` is the
                # exact selection `demote_coldest` pops (§13)
                slots = self._coldest(self.hot)
                pk = self.ens.hot_keys[:, slots]
            self.hot, dem = self._demote(self.hot)
            self._capture_and_append(dem, pk)
            self._c_demotions.inc(int(_fetched(dem.mask, _WRITE_SYNC).sum()))
        # the tail window only covers the last `tail` ring writes; a
        # rebuild is forced before the unindexed backlog outgrows it,
        # else demoted rows would silently fall out of reach
        if not self.background_rebuild:
            if rebuild or self._tail_pressure():
                self._rebuild_inline()
            return
        # double-buffered: publish any finished shadow, then make sure
        # the window still covers the backlog before serving resumes
        if self._shadow_thread is not None \
                and not self._shadow_thread.is_alive():
            self._publish_shadow()
        if self._backlog() > self._tail:
            if self._shadow_thread is not None:
                self._publish_shadow()          # blocks: join + swap
            if self._backlog() > self._tail:
                self._rebuild_inline()          # snapshot was too old
        if (rebuild or self._tail_pressure()) \
                and self._shadow_thread is None:
            self._start_shadow()

    def _maybe_flush(self) -> None:
        n_valid = int(_fetched(self.hot.valid, _WRITE_SYNC).sum())
        if n_valid >= self.flush_watermark * self.hot_capacity:
            self._do_flush(rebuild=False)

    def flush(self, rebuild: bool = True) -> None:
        """Force one demotion flush now.  ``rebuild=False`` still
        rebuilds if skipping would leave rows beyond the tail window.
        With ``background_rebuild`` the re-cluster runs double-buffered
        (shadow build + later publish) instead of inline."""
        self._do_flush(rebuild)

    # ------------------------------------------------------------------
    @property
    def hot_occupancy(self) -> float:
        return float(np.asarray(self.hot.valid).mean())

    @property
    def warm_occupancy(self) -> float:
        return float(np.asarray(self.warm.valid).mean())

    @property
    def occupancy(self) -> float:
        """Drop-in parity with SemanticCache (fraction of total rows)."""
        n = int(np.asarray(self.hot.valid).sum()) \
            + int(np.asarray(self.warm.valid).sum())
        return n / (self.hot_capacity + self.warm_capacity)

    def __len__(self) -> int:
        n = int(np.asarray(self.hot.valid).sum()) \
            + int(np.asarray(self.warm.valid).sum())
        return n + len(self.cold) if self.cold is not None else n


# ---------------------------------------------------------------------------
# §11 refresh helpers (module-level: they run on the refresh thread and
# must only touch the snapshots they are handed)
# ---------------------------------------------------------------------------

def _eval_gate(cand, baseline, eval_ds, tok,
               pol: EmbedderRefreshPolicy) -> Dict[str, object]:
    """Judge the candidate on the held-out slice: absolute
    precision/recall floors plus no-F1-regression against the frozen
    embedder on the *same* slice.  An eval slice without both labels
    cannot support the metrics — fail closed (rollback), never publish
    unjudged."""
    labels = np.asarray(eval_ds.labels)
    if len(labels) == 0 or len(np.unique(labels)) < 2:
        return {"pass": False, "reason": "eval-starved"}
    cand_m = cand.evaluate(eval_ds, tok)
    base_m = baseline.evaluate(eval_ds, tok)
    ok = (cand_m["precision"] >= pol.min_precision
          and cand_m["recall"] >= pol.min_recall
          and cand_m["f1"] >= base_m["f1"] - pol.max_f1_regression)
    return {"pass": bool(ok), "reason": "ok" if ok else "gate-failed",
            "candidate": cand_m, "baseline": base_m}


def _reembed_snapshot(trainer, tok, hot, warm,
                      texts: Dict[int, str]) -> Dict[int, np.ndarray]:
    """Re-embed every snapshot row whose query text is retained.
    Returns value id -> new embedding (the publish grafts them onto the
    then-current tiers by id, so rows evicted since the snapshot are
    simply never looked up)."""
    vids: set = set()
    for state in (hot, warm):
        v = np.asarray(state.value_ids)[np.asarray(state.valid)]
        vids.update(int(x) for x in v.ravel())
    todo = [(v, texts[v]) for v in sorted(vids) if v in texts]
    if not todo:
        return {}
    embs = trainer.embed_texts([t for _, t in todo], tok)
    return {v: embs[i] for i, (v, _) in enumerate(todo)}


def _single_class(ds) -> bool:
    labels = np.asarray(ds.labels)
    return len(labels) == 0 or len(np.unique(labels)) < 2


def _synth_backfill(train, eval_ds, pol: EmbedderRefreshPolicy):
    """Top a thin or class-skewed split up with grammar-synthesized
    paraphrase/distinct pairs (the paper's synthetic augmentation,
    DESIGN.md §6) from ``pol.synth_domain``.  The synthetic pool is
    itself split train/eval with the reservoir's ``eval_frac``
    discipline — but only when the held-out slice is class-starved
    (otherwise the gate keeps judging on pure serving pairs); the
    split is deterministic in ``synth_seed``, so every candidate
    trained from the same reservoir state faces the same gate.
    Returns the augmented ``(train, eval)`` datasets."""
    from repro.core.synth import (
        TemplateGenerator, generate_synthetic_pairs, records_to_dataset,
    )
    from repro.data.corpora import PairDataset, sample_query
    need = max(pol.synth_min_pairs - len(train.labels), 8)
    rng = np.random.default_rng(pol.synth_seed)
    # each seed query yields 2 paraphrase + 2 distinct records
    seeds = [sample_query(rng, pol.synth_domain)
             for _ in range(max(-(-need // 4), 1))]
    synth = records_to_dataset(generate_synthetic_pairs(
        seeds, TemplateGenerator(pol.synth_seed), n_pos=2, n_neg=2))
    perm = np.random.default_rng(pol.synth_seed).permutation(
        len(synth.labels))
    n_eval = int(np.ceil(len(perm) * pol.eval_frac)) \
        if _single_class(eval_ds) else 0
    ev, tr = perm[:n_eval], perm[n_eval:]

    def cat(ds: PairDataset, idx: np.ndarray) -> PairDataset:
        return PairDataset(
            q1=list(ds.q1) + [synth.q1[i] for i in idx],
            q2=list(ds.q2) + [synth.q2[i] for i in idx],
            labels=np.concatenate(
                [np.asarray(ds.labels, np.int32),
                 np.asarray([synth.labels[i] for i in idx], np.int32)]),
            domain=ds.domain)

    return cat(train, tr), cat(eval_ds, ev)
