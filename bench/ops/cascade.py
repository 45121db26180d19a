"""Operations and bytes of one call of the four-op cascade lookup.

The four ops (`repro.cache_service.tiers`, `cascade_lookup`) on a
float32 warm tier: hot-tier brute force, warm centroid scores and the
gathered probe panel (``n_probe`` inverted lists of ``bucket`` rows
plus the ``tail`` window of unindexed rows, per query).  Bytes count what each query must read at least once: the hot
tier and the centroids once per call, the probe panel once per query
(queries probe different lists, so nothing is shared by rule), with
each candidate's key row and its valid, tenant and write-sequence
entries.
"""
from __future__ import annotations


def cascade_cost(q: int, dim: int, hot: int, clusters: int, bucket: int,
                 n_probe: int, tail: int) -> dict:
    """-> {"flops", "bytes"} of one lookup of ``q`` queries."""
    cand = n_probe * bucket + tail
    flops = 2 * q * dim * (hot + clusters + cand)
    nbytes = (hot * (dim * 4 + 9)               # keys, valid, tenant, id
              + clusters * dim * 4              # centroids
              + q * n_probe * bucket * 4        # inverted-list entries
              + q * cand * (dim * 4 + 9)        # panel rows + metadata
              + q * dim * 4)                    # queries
    return {"flops": float(flops), "bytes": float(nbytes)}


def served_shape(tiering: dict, q: int, dim: int) -> dict:
    """`cascade_cost` arguments of a service built from a configuration's
    ``tiering`` group, looked up ``q`` rows at a time.  The tail window
    is the service's: ``flush_size * rebuild_every`` rows, with
    ``flush_size`` a quarter of the hot tier, at most the warm tier."""
    if tiering["warm_dtype"] != "float32":
        raise ValueError("the cascade's operations and bytes are counted "
                         "for a float32 warm tier only")
    return {"q": q, "dim": dim, "hot": tiering["hot_capacity"],
            "clusters": tiering["n_clusters"], "bucket": tiering["bucket"],
            "n_probe": tiering["n_probe"],
            "tail": min(tiering["hot_capacity"] // 4
                        * tiering["rebuild_every"],
                        tiering["warm_capacity"])}


def least_seconds(cost: dict, peak: dict) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bandwidth: no implementation on that chip can be faster."""
    return max(cost["flops"] / peak["bf16_flops"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
