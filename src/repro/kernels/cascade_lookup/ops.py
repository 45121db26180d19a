"""Dispatch between the jnp cascade and the Pallas cascade kernel.

The jnp cascade (`ref.py`) serves every tier configuration on every
platform.  ``use_kernel=True`` runs the Pallas kernel instead —
interpret mode off the TPU — and only the kernel's parity tests set
it: Mosaic refuses the kernel's IVF gathers
(`tests/test_tpu_compile.py`), so no served code reaches it.  The
change that makes the kernel lower flips this one default by platform.
Both share the exact signature, so `tiers.cascade_query` is agnostic.
The ``quantized`` flag selects the int8 warm-panel variant in both
implementations (DESIGN.md §8); callers re-score the returned
``warm_slots`` exactly from the fp32 panel at merge time.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.cascade_lookup import kernel as _kernel
from repro.kernels.cascade_lookup import ref as _ref


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def cascade_lookup(q, q_tenants, thresholds,
                   hot_keys, hot_valid, hot_tenants, hot_value_ids,
                   warm_keys, warm_valid, warm_tenants, warm_value_ids,
                   warm_write_seq, centroids, members, cursor, indexed_total,
                   warm_keys_q=None, warm_scales=None,
                   k: int = 1, n_probe: int = 8, tail: int = 0, *,
                   quantized: bool = False,
                   use_kernel: bool = False,
                   block_n: int = _kernel.DEFAULT_BLOCK_N,
                   warm_block_n: int | None = None):
    """q: (Q, D) unit-norm -> (scores, value_ids, warm_slots, hot_slots,
    hot_hit, hit); see `ref.cascade_lookup`.

    ``block_n`` and ``warm_block_n`` (stream the warm panel in blocks of
    that many rows; None = whole panel) are the kernel's own; the jnp
    cascade ignores them — blocking never changes results.
    """
    if use_kernel:
        return _kernel.cascade_lookup(
            q, q_tenants, thresholds, hot_keys, hot_valid, hot_tenants,
            hot_value_ids, warm_keys, warm_valid, warm_tenants,
            warm_value_ids, warm_write_seq, centroids, members, cursor,
            indexed_total, warm_keys_q, warm_scales, k, n_probe, tail,
            quantized=quantized, block_n=block_n,
            warm_block_n=warm_block_n, interpret=not _on_tpu())
    return _ref.cascade_lookup(
        q, q_tenants, thresholds, hot_keys, hot_valid, hot_tenants,
        hot_value_ids, warm_keys, warm_valid, warm_tenants, warm_value_ids,
        warm_write_seq, centroids, members, cursor, indexed_total,
        warm_keys_q, warm_scales, k, n_probe, tail, quantized=quantized)


def ensemble_lookup(q, weights, q_tenants, thresholds,
                    hot_keys, hot_valid, hot_tenants, hot_value_ids,
                    warm_keys, warm_valid, warm_tenants, warm_value_ids,
                    warm_write_seq, centroids, members, cursor, indexed_total,
                    warm_keys_q=None, warm_scales=None,
                    k: int = 1, n_probe: int = 8, tail: int = 0, *,
                    quantized: bool = False,
                    use_kernel: bool = False,
                    block_n: int = _kernel.DEFAULT_BLOCK_N,
                    warm_block_n: int | None = None):
    """E-panel fused ensemble dispatch (DESIGN.md §13): q (E, Q, D)
    stacked unit-norm queries, weights (Q, E) mixture weights, key
    panels stacked (E, N, D) with shared per-slot metadata and
    pilot-built IVF -> the same 6-tuple as `cascade_lookup` with the
    weighted fused score; see `ref.ensemble_lookup`.

    Dispatch rules match `cascade_lookup`: the jnp cascade unless
    ``use_kernel`` (interpret mode off the TPU).
    """
    if use_kernel:
        return _kernel.cascade_lookup_ensemble(
            q, weights, q_tenants, thresholds, hot_keys, hot_valid,
            hot_tenants, hot_value_ids, warm_keys, warm_valid, warm_tenants,
            warm_value_ids, warm_write_seq, centroids, members, cursor,
            indexed_total, warm_keys_q, warm_scales, k, n_probe, tail,
            quantized=quantized, block_n=block_n,
            warm_block_n=warm_block_n, interpret=not _on_tpu())
    return _ref.ensemble_lookup(
        q, weights, q_tenants, thresholds, hot_keys, hot_valid, hot_tenants,
        hot_value_ids, warm_keys, warm_valid, warm_tenants, warm_value_ids,
        warm_write_seq, centroids, members, cursor, indexed_total,
        warm_keys_q, warm_scales, k, n_probe, tail, quantized=quantized)
