"""The typed construction surface (DESIGN.md §14.4).

``CacheConfig`` is a frozen dataclass of grouped sub-configs, each
validating its own fields at construction; the legacy flat-kwargs
constructor maps onto it through ``CacheConfig.from_kwargs`` (kept one
release, warns ``DeprecationWarning`` once per process).  These tests
pin the contract: field validation fires at dataclass construction,
the legacy mapping covers every renamed key, unknown kwargs are a
``TypeError`` not a silent drop, and the config path refuses to mix
with flat kwargs."""
import dataclasses
import warnings

import pytest

from repro.cache_service import (
    CacheConfig, CacheService, EnsembleConfig, LearningConfig,
    ShardingConfig, StalenessConfig, TieringConfig,
)
from repro.cache_service.feedback import FeedbackConfig


# ---------------------------------------------------------------------------
# field validation fires in __post_init__
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(dim=0), dict(dim=-4),
    dict(dim=16, topk=0),
    dict(dim=16, threshold=0.0), dict(dim=16, threshold=1.2),
    dict(dim=16, admission_margin=-0.1),
])
def test_cache_config_rejects_bad_top_level(bad):
    with pytest.raises(ValueError):
        CacheConfig(**bad)


@pytest.mark.parametrize("bad", [
    dict(hot_capacity=0), dict(warm_capacity=0),
    dict(n_clusters=0), dict(bucket=0), dict(n_probe=0),
    dict(flush_watermark=0.0), dict(flush_watermark=1.5),
    dict(flush_size=0), dict(rebuild_every=0),
    dict(warm_dtype="bfloat16"), dict(cold_capacity=-1),
])
def test_tiering_config_rejects_bad_fields(bad):
    with pytest.raises(ValueError):
        TieringConfig(**bad)


def test_sub_config_validation():
    with pytest.raises(ValueError):
        ShardingConfig(shard_axis="")
    with pytest.raises(ValueError):
        EnsembleConfig(embedders=0)
    with pytest.raises(ValueError):
        StalenessConfig(default_ttl=0.0)
    # frozen: configs are immutable once built
    with pytest.raises(dataclasses.FrozenInstanceError):
        CacheConfig(dim=16).dim = 32


# ---------------------------------------------------------------------------
# legacy flat-kwargs mapping
# ---------------------------------------------------------------------------

def test_from_kwargs_groups_every_renamed_key():
    fb = FeedbackConfig()
    cfg = CacheConfig.from_kwargs(
        32, threshold=0.9, hot_capacity=64, warm_capacity=256,
        cold_capacity=512, learned_admission=True,
        feedback_config=fb,                  # renamed -> learning.feedback
        embedders=3, ensemble_weights=None,  # renamed -> ensemble.weights
        default_ttl=30.0,
    )
    assert cfg.dim == 32 and cfg.threshold == 0.9
    assert cfg.tiering.hot_capacity == 64
    assert cfg.tiering.cold_capacity == 512
    assert cfg.learning.learned_admission
    assert cfg.learning.feedback is fb
    assert cfg.ensemble.embedders == 3
    assert cfg.staleness.default_ttl == 30.0


def test_from_kwargs_rejects_unknown_keyword():
    with pytest.raises(TypeError, match="unknown CacheService kwargs"):
        CacheConfig.from_kwargs(32, capacty=64)    # typo must not be dropped


def test_legacy_kwargs_construction_warns_once():
    CacheService._kwargs_warned = False            # reset the process latch
    with pytest.warns(DeprecationWarning, match="flat-kwargs"):
        svc = CacheService(dim=16, hot_capacity=8, warm_capacity=32,
                           n_clusters=2, bucket=16)
    assert svc.config.tiering.hot_capacity == 8
    with warnings.catch_warnings():
        warnings.simplefilter("error")             # second build: silent
        CacheService(dim=16, hot_capacity=8, warm_capacity=32,
                     n_clusters=2, bucket=16)


def test_config_path_rejects_extra_kwargs():
    cfg = CacheConfig(dim=16)
    with pytest.raises(TypeError, match="no extra kwargs"):
        CacheService(cfg, hot_capacity=64)


def test_config_and_legacy_paths_build_identically():
    cfg = CacheConfig(dim=16, threshold=0.9,
                      tiering=TieringConfig(hot_capacity=8, warm_capacity=32,
                                            n_clusters=2, bucket=16),
                      learning=LearningConfig(conformal=True))
    a = CacheService(cfg)
    CacheService._kwargs_warned = True             # silence the shim
    b = CacheService(dim=16, threshold=0.9, hot_capacity=8,
                     warm_capacity=32, n_clusters=2, bucket=16,
                     conformal=True)
    assert a.config == b.config
