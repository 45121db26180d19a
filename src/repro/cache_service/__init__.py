"""Tiered multi-tenant cache service (beyond-paper subsystem).

A `CacheService` facade composes a hot exact tier, a warm IVF tier with
demotion + double-buffered rebuild, per-tenant thresholds/admission,
and host-side response GC — the production serving layer between the
store primitives (repro.core) and the LLM engine (repro.serving).  The
serving pipeline drives any backend through the typed ``CacheBackend``
protocol (plan/commit lifecycle, DESIGN.md §7).
"""
from repro.cache_service.config import (
    CacheConfig, EnsembleConfig, LearningConfig, ShardingConfig,
    StalenessConfig, TieringConfig,
)
from repro.cache_service.feedback import (
    ConformalWindow, FeedbackAccumulator, FeedbackConfig, RefitReport,
    TenantReservoir, record_refit,
)
from repro.cache_service.feedback import PairReservoir
from repro.cache_service.cold import ColdFetch, ColdTier, Promotion
from repro.cache_service.policy import (
    ColdRoutingPolicy, EmbedderRefreshPolicy, PolicyTable, TenantPolicy,
)
from repro.cache_service.protocol import (
    CacheBackend, CacheCapabilities, CachePlan, CacheRequest,
    CommitReceipt, MaintenanceReport, coalesce_misses, ungrouped_misses,
)
from repro.cache_service.service import (
    CacheService, ServiceStats,
)
from repro.cache_service.tiers import (
    CascadeResult, Demoted, HotState, WarmState, cascade_query,
    demote_coldest, evict_tenant, hot_insert, hot_insert_batch,
    hot_touch, init_hot, init_warm, init_warm_sharded, mask_expired,
    place_warm_sharded,
    publish_reembedded_keys, quantize_rows, reap_expired, requantize,
    stack_warm, warm_append, warm_append_sharded, warm_occupancy,
    warm_publish_index, warm_rebuild, warm_rebuild_sharded,
)

__all__ = [
    "CacheService", "ServiceStats",
    "CacheConfig", "TieringConfig", "ShardingConfig", "LearningConfig",
    "EnsembleConfig", "StalenessConfig", "ConformalWindow",
    "ColdFetch", "ColdRoutingPolicy", "ColdTier", "Promotion",
    "EmbedderRefreshPolicy", "PolicyTable", "TenantPolicy",
    "FeedbackAccumulator", "FeedbackConfig", "PairReservoir",
    "RefitReport", "TenantReservoir", "record_refit",
    "CacheBackend", "CacheCapabilities", "CachePlan", "CacheRequest",
    "CommitReceipt", "MaintenanceReport", "coalesce_misses",
    "ungrouped_misses",
    "CascadeResult", "Demoted", "HotState", "WarmState", "cascade_query",
    "demote_coldest", "evict_tenant", "hot_insert", "hot_insert_batch",
    "hot_touch", "init_hot", "init_warm", "init_warm_sharded",
    "mask_expired", "place_warm_sharded",
    "publish_reembedded_keys", "quantize_rows", "reap_expired",
    "requantize", "stack_warm", "warm_append", "warm_append_sharded",
    "warm_occupancy", "warm_publish_index", "warm_rebuild",
    "warm_rebuild_sharded",
]
