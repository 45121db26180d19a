"""Functional JAX vector store — the cache's TPU-resident index.

The paper uses Redis vector search; the TPU-native analogue (DESIGN.md
§3) is a fixed-capacity store whose state is a pytree of device arrays,
so insert/query/evict are pure jittable functions and the whole store
shards under pjit (corpus rows over the `model` axis — each shard
computes a local top-k that a tiny merge resolves).

Eviction policy: free slot first, else least-recently-used (a lamport
clock updated on hits).  TTL eviction is a pure mask update.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class StoreState(NamedTuple):
    keys: jax.Array        # (N, D) float32, unit-norm rows
    valid: jax.Array       # (N,)  bool
    last_used: jax.Array   # (N,)  int32 lamport clock
    inserted_at: jax.Array  # (N,) int32
    value_ids: jax.Array   # (N,)  int32 host-side response index
    clock: jax.Array       # ()    int32


class QueryResult(NamedTuple):
    scores: jax.Array      # (Q, k) cosine similarity, desc
    slots: jax.Array       # (Q, k) store row indices
    value_ids: jax.Array   # (Q, k)
    hit: jax.Array         # (Q,)   best score >= threshold


def init_store(capacity: int, dim: int) -> StoreState:
    return StoreState(
        keys=jnp.zeros((capacity, dim), jnp.float32),
        valid=jnp.zeros((capacity,), bool),
        last_used=jnp.zeros((capacity,), jnp.int32),
        inserted_at=jnp.zeros((capacity,), jnp.int32),
        value_ids=jnp.full((capacity,), -1, jnp.int32),
        clock=jnp.zeros((), jnp.int32),
    )


def store_axes() -> StoreState:
    """Logical sharding axes (encoded strings) for the store pytree."""
    return StoreState(
        keys="corpus,.", valid="corpus", last_used="corpus",
        inserted_at="corpus", value_ids="corpus", clock="",
    )


def _choose_slot(state: StoreState) -> jax.Array:
    """First invalid slot, else LRU."""
    has_free = jnp.any(~state.valid)
    first_free = jnp.argmax(~state.valid)          # first True
    lru = jnp.argmin(jnp.where(state.valid, state.last_used, jnp.iinfo(jnp.int32).max))
    return jnp.where(has_free, first_free, lru).astype(jnp.int32)


def insert(state: StoreState, emb: jax.Array, value_id: jax.Array) -> StoreState:
    """Insert one unit-norm embedding (D,) with its response id."""
    emb = emb.astype(jnp.float32)
    emb = emb / jnp.maximum(jnp.linalg.norm(emb), 1e-9)
    slot = _choose_slot(state)
    clock = state.clock + 1
    return StoreState(
        keys=state.keys.at[slot].set(emb),
        valid=state.valid.at[slot].set(True),
        last_used=state.last_used.at[slot].set(clock),
        inserted_at=state.inserted_at.at[slot].set(clock),
        value_ids=state.value_ids.at[slot].set(value_id.astype(jnp.int32)),
        clock=clock,
    )


def insert_batch(state: StoreState, embs: jax.Array,
                 value_ids: jax.Array) -> StoreState:
    """Sequential batch insert (slot choice is order-dependent)."""

    def body(s, xs):
        e, vid = xs
        return insert(s, e, vid), None

    state, _ = jax.lax.scan(body, state, (embs, value_ids))
    return state


def query(state: StoreState, q: jax.Array, threshold: float,
          k: int = 1, topk_fn=None) -> QueryResult:
    """q: (Q, D).  Returns top-k cosine matches among valid rows.

    topk_fn(q, keys, valid, k) -> (scores, slots): injection point for
    the Pallas `cosine_topk` kernel; defaults to the jnp reference.
    """
    qn = q.astype(jnp.float32)
    qn = qn / jnp.maximum(jnp.linalg.norm(qn, axis=-1, keepdims=True), 1e-9)
    if topk_fn is None:
        from repro.kernels.cosine_topk import ops as _ops
        topk_fn = _ops.cosine_topk
    scores, slots = topk_fn(qn, state.keys, state.valid, k)
    value_ids = state.value_ids[slots]
    hit = scores[:, 0] >= threshold
    return QueryResult(scores=scores, slots=slots, value_ids=value_ids, hit=hit)


def query_sharded(state: StoreState, q: jax.Array, threshold: float,
                  k: int, mesh, axis: str = "model") -> QueryResult:
    """Distributed lookup with an explicit local-topk + tiny-merge
    schedule (beyond-paper §Perf optimization, DESIGN.md §3).

    GSPMD's auto-partition of `query` all-gathers the full (Q, N) score
    matrix across the corpus axis; this shard_map version computes a
    LOCAL top-k per corpus shard and all-gathers only (Q, 2k) candidate
    scores+ids per device — the collective shrinks from O(Q·N) to
    O(Q·k·shards).  The corpus stays sharded over ``axis``; queries may
    stay batch-sharded over the other mesh axes.  The local-topk +
    tiny-merge step itself is `core.distrib.merge_local_topk`, shared
    with the tiered cache's sharded warm lookup (DESIGN.md §8).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.distrib import merge_local_topk

    qn = q.astype(jnp.float32)
    qn = qn / jnp.maximum(jnp.linalg.norm(qn, axis=-1, keepdims=True), 1e-9)
    n_total = state.keys.shape[0]
    n_shards = mesh.shape[axis]
    shard_n = n_total // n_shards
    other = tuple(a for a in mesh.axis_names if a != axis)
    batch_axes = tuple(a for a in other
                       if q.shape[0] % mesh.shape[a] == 0) or None

    def local(keys, valid, value_ids, qloc):
        # keys: (N/shards, D) this shard; qloc: (Q_loc, D)
        scores = qloc @ keys.T                                  # (Q, N_loc)
        scores = jnp.where(valid[None, :], scores, -1e30)
        s, i_loc = jax.lax.top_k(scores, k)                     # local top-k
        vals = value_ids[i_loc]                                 # (Q, k)
        i_glob = i_loc + jax.lax.axis_index(axis) * shard_n
        # tiny merge: gather only (Q, k) candidates from every shard
        return merge_local_topk(axis, k, s, i_glob, vals)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis),
                  P(batch_axes, None)),
        out_specs=(P(batch_axes, None), P(batch_axes, None),
                   P(batch_axes, None)),
        check_vma=False)
    scores, slots, value_ids = fn(state.keys, state.valid, state.value_ids,
                                  qn)
    hit = scores[:, 0] >= threshold
    return QueryResult(scores=scores, slots=slots, value_ids=value_ids,
                       hit=hit)


def touch(state: StoreState, slots: jax.Array, hit: jax.Array) -> StoreState:
    """LRU bump for hit slots (slots: (Q,), hit: (Q,))."""
    clock = state.clock + 1
    safe = jnp.where(hit, slots, 0)
    new_last = state.last_used.at[safe].max(
        jnp.where(hit, clock, jnp.zeros_like(clock)))
    return state._replace(last_used=new_last, clock=clock)


def evict_older_than(state: StoreState, max_age: int) -> StoreState:
    """TTL policy: invalidate entries older than ``max_age`` ticks."""
    expired = (state.clock - state.inserted_at) > max_age
    return state._replace(valid=state.valid & ~expired)


def occupancy(state: StoreState) -> jax.Array:
    return jnp.mean(state.valid.astype(jnp.float32))
