"""Pure-jnp oracle for the fused cascade lookup.

This is the tiered cache's original four-op path (hot exact top-k, warm
centroid probe, IVF bucket gather + unindexed-tail scan, best-of-tiers
merge — `cache_service/tiers.py`) expressed over plain arrays, so the
Pallas kernel and the NamedTuple-based cascade can both be checked
against one reference.  Candidate ordering matches `jax.lax.top_k`
tie-breaking (lowest index wins) everywhere, which is what the kernel's
masked-argmax rounds reproduce.

Queries are expected unit-norm float32 (the caller normalizes once; the
unfused tiers path normalizes per tier, but `_unit` is idempotent up to
bit-identity on already-unit rows, so parity holds).

``quantized=True`` scores the warm panel from its int8 symmetric
per-row quantization (``warm_keys_q`` + ``warm_scales``) with fp32
accumulation — the selection then runs on approximate scores whose
per-candidate error is bounded by ``amax·sqrt(D)/254`` (DESIGN.md §8);
the caller re-scores the selected rows exactly from the fp32 panel at
merge time, which is why every return includes ``warm_slots`` (the warm
row of each merged candidate, -1 for hot/invalid entries).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

NEG = -1e30


@jax.jit
def cosine(q, keys):
    """Scores of unit-norm queries q (Q, D) against keys shared by every
    query (N, D), or gathered per query (Q, N, D) -> (Q, N).

    Written as an elementwise product summed over D rather than a dot:
    each score is then the same f32 sum whatever N is and however the
    panel is cut into blocks, so the kernel's streamed blocks, this
    oracle's whole panel and the tiers' four-op path agree bit for bit.
    XLA's CPU dot rounds differently with the panel's shape, and on a
    TPU an f32 dot defaults to bf16 passes; this form is f32 on both.
    Jitted, so that called eagerly the product and the sum still fuse
    into one loop — run as two separate ops they round differently.
    """
    if keys.ndim == 2:
        keys = keys[None]
    return jnp.sum(q[:, None, :] * keys, axis=-1)


@jax.jit
def fuse(panels, weights):
    """Weighted cross-panel sum of E per-embedder (Q, N) score panels
    under (Q, E) mixture weights -> (Q, N), in the same elementwise
    form as `cosine` (bitwise independent of N)."""
    return jnp.sum(jnp.stack(panels, -1) * weights[:, None, :], axis=-1)


def cascade_lookup(q, q_tenants, thresholds,
                   hot_keys, hot_valid, hot_tenants, hot_value_ids,
                   warm_keys, warm_valid, warm_tenants, warm_value_ids,
                   warm_write_seq, centroids, members, cursor, indexed_total,
                   warm_keys_q=None, warm_scales=None,
                   k: int = 1, n_probe: int = 8, tail: int = 0,
                   quantized: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                              jax.Array, jax.Array]:
    """q: (Q, D) unit-norm; q_tenants/thresholds: (Q,).

    Returns (scores (Q, k), value_ids (Q, k), warm_slots (Q, k),
    hot_slots (Q,), hot_hit (Q,), hit (Q,)) — ``warm_slots`` is -1 for
    candidates answered by the hot tier (or padding).
    """
    q = q.astype(jnp.float32)
    q_tenants = q_tenants.astype(jnp.int32)
    Q = q.shape[0]
    rows = jnp.arange(Q)[:, None]

    # hot tier: exact tenant-masked top-k
    hs_all = cosine(q, hot_keys)                                   # (Q, Nh)
    ok = hot_valid[None, :] & (hot_tenants[None, :] == q_tenants[:, None])
    hs_all = jnp.where(ok, hs_all, NEG)
    hs, hslots = jax.lax.top_k(hs_all, k)
    hvids = jnp.where(hs > NEG / 2, hot_value_ids[hslots], -1)

    # warm tier: IVF probe + unindexed tail
    cap = warm_keys.shape[0]
    n_clusters, bucket = members.shape
    n_probe = min(n_probe, n_clusters)
    csims = cosine(q, centroids)                                   # (Q, K)
    _, probes = jax.lax.top_k(csims, n_probe)
    cand = members[probes].reshape(Q, n_probe * bucket)
    is_tail = jnp.zeros(cand.shape, bool)
    if tail:
        tail_idx = (cursor - 1 - jnp.arange(tail, dtype=jnp.int32)) % cap
        unindexed = warm_write_seq[tail_idx] > indexed_total
        tail_cand = jnp.where(unindexed, tail_idx, -1)
        cand = jnp.concatenate(
            [cand, jnp.broadcast_to(tail_cand[None, :], (Q, tail))], axis=1)
        is_tail = jnp.concatenate(
            [is_tail, jnp.ones((Q, tail), bool)], axis=1)
    safe = jnp.clip(cand, 0, cap - 1)
    ok = (cand >= 0) & warm_valid[safe] \
        & (warm_tenants[safe] == q_tenants[:, None]) \
        & (is_tail | (warm_write_seq[safe] <= indexed_total))
    if quantized:
        # int8 panel, fp32 accumulation: dequantize per candidate row
        panel = warm_keys_q[safe].astype(jnp.float32)
        wscores = cosine(q, panel) * warm_scales[safe]
    else:
        wscores = cosine(q, warm_keys[safe])
    wscores = jnp.where(ok, wscores, NEG)
    ws, wi = jax.lax.top_k(wscores, k)
    wslots = safe[rows, wi]
    wvids = jnp.where(ws > NEG / 2, warm_value_ids[wslots], -1)
    wslots = jnp.where(ws > NEG / 2, wslots, -1)

    # best-of-tiers merge (hot side first, so ties resolve hot)
    all_s = jnp.concatenate([hs, ws], axis=1)                      # (Q, 2k)
    all_v = jnp.concatenate([hvids, wvids], axis=1)
    all_w = jnp.concatenate([jnp.full((Q, k), -1, jnp.int32),
                             wslots.astype(jnp.int32)], axis=1)
    s, i = jax.lax.top_k(all_s, k)
    vids = all_v[rows, i]
    out_wslots = all_w[rows, i]
    hit = s[:, 0] >= thresholds
    hot_hit = hit & (i[:, 0] < k)
    return s, vids, out_wslots, hslots[:, 0], hot_hit, hit


def ensemble_lookup(q, weights, q_tenants, thresholds,
                    hot_keys, hot_valid, hot_tenants, hot_value_ids,
                    warm_keys, warm_valid, warm_tenants, warm_value_ids,
                    warm_write_seq, centroids, members, cursor, indexed_total,
                    warm_keys_q=None, warm_scales=None,
                    k: int = 1, n_probe: int = 8, tail: int = 0,
                    quantized: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                               jax.Array, jax.Array]:
    """E-panel four-op oracle for the fused ensemble cascade
    (DESIGN.md §13): the weighted fused similarity over E embedder key
    panels, routed once on the *pilot* embedder (panel 0).

    q: (E, Q, D) unit-norm, one query embedding per embedder;
    weights: (Q, E) per-query mixture weights (the service resolves
    them per tenant); hot_keys: (E, Nh, D); warm_keys: (E, cap, D)
    (``warm_keys_q``/``warm_scales``: (E, cap, D) int8 / (E, cap) when
    ``quantized``).  All per-slot metadata (valid/tenant/value-id/
    write-seq columns) and the IVF (centroids + inverted lists, built
    from the pilot panel) are shared across panels — the panels are E
    views of the *same* rows, kept row-aligned by construction
    (`tiers.EnsembleState`).

    The fused score of a candidate row is
    ``sum_e weights[q, e] * cos(q_e, key_e[row])``.  The cross-panel
    weighted sum is one reduction over the stacked per-panel scores
    (`fuse`) — a single primitive, so eager and jitted evaluation agree
    bitwise and the kernel reproduces it exactly (an unrolled
    multiply-add chain is NOT fusion-stable: XLA reassociates it
    differently across surrounding graphs).  Masking applies after the
    weighted sum.  The probe runs on the unweighted pilot query against
    the shared (pilot-built) centroids, so the bucket gather is issued
    once and amortized over all E panels.  Returns the same 6-tuple as
    `cascade_lookup`, with scores fused.
    """
    E = q.shape[0]
    q = q.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    q_tenants = q_tenants.astype(jnp.int32)
    Q = q.shape[1]
    rows = jnp.arange(Q)[:, None]

    # hot tier: fused tenant-masked top-k over the stacked panels
    hot_pans = [cosine(q[e], hot_keys[e]) for e in range(E)]       # E×(Q, Nh)
    hs_all = fuse(hot_pans, weights)
    ok = hot_valid[None, :] & (hot_tenants[None, :] == q_tenants[:, None])
    hs_all = jnp.where(ok, hs_all, NEG)
    hs, hslots = jax.lax.top_k(hs_all, k)
    hvids = jnp.where(hs > NEG / 2, hot_value_ids[hslots], -1)

    # warm tier: pilot-routed IVF probe + unindexed tail, fused score
    cap = warm_keys.shape[1] if not quantized else warm_keys_q.shape[1]
    n_clusters, bucket = members.shape
    n_probe = min(n_probe, n_clusters)
    csims = cosine(q[0], centroids)             # pilot routing (Q, K)
    _, probes = jax.lax.top_k(csims, n_probe)
    cand = members[probes].reshape(Q, n_probe * bucket)
    is_tail = jnp.zeros(cand.shape, bool)
    if tail:
        tail_idx = (cursor - 1 - jnp.arange(tail, dtype=jnp.int32)) % cap
        unindexed = warm_write_seq[tail_idx] > indexed_total
        tail_cand = jnp.where(unindexed, tail_idx, -1)
        cand = jnp.concatenate(
            [cand, jnp.broadcast_to(tail_cand[None, :], (Q, tail))], axis=1)
        is_tail = jnp.concatenate(
            [is_tail, jnp.ones((Q, tail), bool)], axis=1)
    safe = jnp.clip(cand, 0, cap - 1)
    ok = (cand >= 0) & warm_valid[safe] \
        & (warm_tenants[safe] == q_tenants[:, None]) \
        & (is_tail | (warm_write_seq[safe] <= indexed_total))

    def _panel(e):
        if quantized:
            pan = warm_keys_q[e][safe].astype(jnp.float32)
            return cosine(q[e], pan) * warm_scales[e][safe]
        return cosine(q[e], warm_keys[e][safe])

    wscores = fuse([_panel(e) for e in range(E)], weights)
    wscores = jnp.where(ok, wscores, NEG)
    ws, wi = jax.lax.top_k(wscores, k)
    wslots = safe[rows, wi]
    wvids = jnp.where(ws > NEG / 2, warm_value_ids[wslots], -1)
    wslots = jnp.where(ws > NEG / 2, wslots, -1)

    # best-of-tiers merge (hot side first, so ties resolve hot)
    all_s = jnp.concatenate([hs, ws], axis=1)                      # (Q, 2k)
    all_v = jnp.concatenate([hvids, wvids], axis=1)
    all_w = jnp.concatenate([jnp.full((Q, k), -1, jnp.int32),
                             wslots.astype(jnp.int32)], axis=1)
    s, i = jax.lax.top_k(all_s, k)
    vids = all_v[rows, i]
    out_wslots = all_w[rows, i]
    hit = s[:, 0] >= thresholds
    hot_hit = hit & (i[:, 0] < k)
    return s, vids, out_wslots, hslots[:, 0], hot_hit, hit
