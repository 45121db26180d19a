"""Pure-JAX device half of the tiered multi-tenant cache.

Two tiers share one geometry (unit-norm cosine keys) and one id space
(host-side ``value_ids``):

  * HOT  — a small flat store that absorbs every admitted insert and
    answers with exact brute-force top-k.  Rows carry a tenant-id
    column; lookups mask on it, so one set of device arrays serves any
    number of logical caches with zero per-tenant recompiles.
  * WARM — a large ring buffer indexed by an IVF (centroids + fixed
    bucket inverted lists).  Cold hot-tier rows are *demoted* here in
    fixed-size flushes; the IVF is rebuilt periodically (jittable
    k-means), and rows appended since the last rebuild stay reachable
    through a fixed-size brute-force *tail* window, so recall does not
    degrade between rebuilds.

Every operation is a pure function over NamedTuple pytrees with static
shapes — insert, demote, append, rebuild and the cascaded lookup all
jit once per shape and shard like the flat store (rows over `model`).

Cascade semantics: one jitted call scores both tiers and returns the
best of the two top-k sets, plus provenance (``hot_hit``) so the host
only bumps hot-tier LRU clocks.  Scores are cosine in both tiers, so
"hot first, warm fallback" and "max over tiers" pick the same answers.
`cascade_query` selects between the four-op XLA composition and the
fused Pallas kernel (`kernels/cascade_lookup`, DESIGN.md §3) — same
results, one kernel launch.

Scale-out (DESIGN.md §8): the warm tier also exists in a *sharded*
form — a stacked ``WarmState`` whose every leaf carries a leading
``shards`` axis, one independent ring + local IVF per shard, laid over
the mesh ``model`` axis by ``cascade_query(..., mesh=...)`` via
shard_map.  Each shard probes its own centroids and computes a local
top-k (the fused kernel runs per shard on exactly the warm slice its
VMEM budget assumes); the only collective is the tiny
(Q, k·shards) candidate merge shared with `store.query_sharded`
(`core.distrib`).  The hot tier stays replicated and is attributed to
shard 0 so the merge never sees duplicate hot candidates.  The warm
panel can additionally be scanned from an int8 symmetric per-row
quantization (``keys_q``/``scales``, maintained on append) with the
selected rows re-scored exactly from the fp32 keys at merge time.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import ivf as ivf_lib
from repro.kernels.cascade_lookup.ref import cosine, fuse

NEG = -1e30


class HotState(NamedTuple):
    keys: jax.Array        # (N, D) float32, unit-norm rows
    valid: jax.Array       # (N,)  bool
    tenants: jax.Array     # (N,)  int32, -1 when invalid
    last_used: jax.Array   # (N,)  int32 lamport clock
    inserted_at: jax.Array  # (N,) int32
    value_ids: jax.Array   # (N,)  int32 host-side response index
    clock: jax.Array       # ()    int32
    expires_at: jax.Array  # (N,)  float32 wall-clock expiry, +inf = no TTL


class WarmState(NamedTuple):
    """One warm ring + IVF.  In the sharded tier every leaf gains a
    leading ``shards`` axis (one independent ring/index per shard);
    `cascade_query` detects the stacked form by ``keys.ndim == 3``."""
    keys: jax.Array        # (Nw, D) float32 unit-norm
    valid: jax.Array       # (Nw,) bool
    tenants: jax.Array     # (Nw,) int32
    value_ids: jax.Array   # (Nw,) int32
    write_seq: jax.Array   # (Nw,) int32 1-based global write sequence
    cursor: jax.Array      # ()    int32 next ring position
    total: jax.Array       # ()    int32 total rows ever appended
    centroids: jax.Array   # (K, D)
    members: jax.Array     # (K, bucket) int32 row ids, -1 empty
    sizes: jax.Array       # (K,) int32
    indexed_total: jax.Array  # () int32: `total` at the last rebuild
    keys_q: jax.Array      # (Nw, D) int8 symmetric per-row quantization
    scales: jax.Array      # (Nw,) float32 per-row dequant scale
    expires_at: jax.Array  # (Nw,) float32 wall-clock expiry, +inf = no TTL


class Demoted(NamedTuple):
    keys: jax.Array        # (m, D)
    value_ids: jax.Array   # (m,)
    tenants: jax.Array     # (m,)
    mask: jax.Array        # (m,) bool — False rows are padding
    # per-row expiry riding along the demotion (None = no TTL anywhere,
    # kept optional so TTL-free callers build Demoted unchanged)
    expires: jax.Array | None = None


class CascadeResult(NamedTuple):
    scores: jax.Array      # (Q, k) best-of-both-tiers cosine, desc
    value_ids: jax.Array   # (Q, k) -1 where no candidate
    hot_slots: jax.Array   # (Q,)   hot-tier row of the hot top-1
    hot_hit: jax.Array     # (Q,)   hit answered by the hot tier
    hit: jax.Array         # (Q,)   best score >= per-query threshold


# one cosine geometry everywhere: share the flat/IVF normalizer
_unit = ivf_lib._unit


# ---------------------------------------------------------------------------
# hot tier
# ---------------------------------------------------------------------------

def init_hot(capacity: int, dim: int) -> HotState:
    return HotState(
        keys=jnp.zeros((capacity, dim), jnp.float32),
        valid=jnp.zeros((capacity,), bool),
        tenants=jnp.full((capacity,), -1, jnp.int32),
        last_used=jnp.zeros((capacity,), jnp.int32),
        inserted_at=jnp.zeros((capacity,), jnp.int32),
        value_ids=jnp.full((capacity,), -1, jnp.int32),
        clock=jnp.zeros((), jnp.int32),
        expires_at=jnp.full((capacity,), jnp.inf, jnp.float32),
    )


def hot_axes() -> HotState:
    """Logical sharding axes (encoded strings) for the hot pytree."""
    return HotState(keys="corpus,.", valid="corpus", tenants="corpus",
                    last_used="corpus", inserted_at="corpus",
                    value_ids="corpus", clock="", expires_at="corpus")


def _choose_slot(state: HotState) -> jax.Array:
    has_free = jnp.any(~state.valid)
    first_free = jnp.argmax(~state.valid)
    lru = jnp.argmin(jnp.where(state.valid, state.last_used,
                               jnp.iinfo(jnp.int32).max))
    return jnp.where(has_free, first_free, lru).astype(jnp.int32)


def hot_insert(state: HotState, emb: jax.Array, value_id: jax.Array,
               tenant: jax.Array, expires: jax.Array | None = None
               ) -> Tuple[HotState, jax.Array]:
    """Insert one embedding; ``value_id < 0`` is an admission skip (no-op).

    ``expires`` (float32 wall-clock, None = +inf) stamps the row's TTL
    deadline; `mask_expired` hides it at plan time and `reap_expired`
    frees it on the maintenance tick.  Returns (state,
    evicted_value_id) — the response id of an overwritten valid slot
    (else -1) so the host can free its string.
    """
    emb = _unit(emb.astype(jnp.float32))
    exp = jnp.asarray(jnp.inf if expires is None else expires, jnp.float32)
    slot = _choose_slot(state)
    clock = state.clock + 1
    skip = value_id < 0
    evicted = jnp.where(~skip & state.valid[slot], state.value_ids[slot], -1)
    new = HotState(
        keys=state.keys.at[slot].set(emb),
        valid=state.valid.at[slot].set(True),
        tenants=state.tenants.at[slot].set(tenant.astype(jnp.int32)),
        last_used=state.last_used.at[slot].set(clock),
        inserted_at=state.inserted_at.at[slot].set(clock),
        value_ids=state.value_ids.at[slot].set(value_id.astype(jnp.int32)),
        clock=clock,
        expires_at=state.expires_at.at[slot].set(exp),
    )
    state = jax.tree_util.tree_map(
        lambda old, upd: jnp.where(skip, old, upd), state, new)
    return state, evicted.astype(jnp.int32)


def hot_insert_batch(state: HotState, embs: jax.Array, value_ids: jax.Array,
                     tenants: jax.Array,
                     expires: jax.Array | None = None
                     ) -> Tuple[HotState, jax.Array]:
    """Sequential batch insert.  Returns (state, evicted (M,) int32)."""
    if expires is None:
        expires = jnp.full(embs.shape[:1], jnp.inf, jnp.float32)

    def body(s, xs):
        e, vid, t, exp = xs
        s, ev = hot_insert(s, e, vid, t, exp)
        return s, ev

    state, evicted = jax.lax.scan(body, state,
                                  (embs, value_ids, tenants, expires))
    return state, evicted


def hot_touch(state: HotState, slots: jax.Array, hit: jax.Array) -> HotState:
    """LRU bump for hit slots (slots: (Q,), hit: (Q,))."""
    clock = state.clock + 1
    safe = jnp.where(hit, slots, 0)
    new_last = state.last_used.at[safe].max(
        jnp.where(hit, clock, jnp.zeros_like(clock)))
    return state._replace(last_used=new_last, clock=clock)


def hot_query(state: HotState, q: jax.Array, q_tenants: jax.Array,
              k: int = 1) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact tenant-masked top-k.  q: (Q, D), q_tenants: (Q,) int32."""
    qn = _unit(q.astype(jnp.float32))
    scores = cosine(qn, state.keys)                               # (Q, N)
    ok = state.valid[None, :] & (state.tenants[None, :]
                                 == q_tenants[:, None])
    scores = jnp.where(ok, scores, NEG)
    s, slots = jax.lax.top_k(scores, k)
    vids = jnp.where(s > NEG / 2, state.value_ids[slots], -1)
    return s, slots, vids


def coldest_slots(state: HotState, m: int) -> jax.Array:
    """The m coldest hot slots in demotion order — the exact selection
    `demote_coldest` pops, exposed so the ensemble flush can gather the
    same rows' panel keys before the demote (DESIGN.md §13)."""
    big = jnp.iinfo(jnp.int32).max
    # int32 throughout: a float32 cast would blur LRU ordering once the
    # clock passes 2^24; invalid rows sort last via the sentinel
    lu = jnp.where(state.valid, state.last_used, big)
    ins = jnp.where(state.valid, state.inserted_at, big)
    return jnp.lexsort((ins, lu))[:m]                             # coldest


def demote_coldest(state: HotState, m: int) -> Tuple[HotState, Demoted]:
    """Pop the m least-recently-used valid rows for warm-tier flush.

    Ties in ``last_used`` — common after a batched `hot_touch`, which
    stamps every hit slot with the same clock — break on the insertion
    sequence (oldest ``inserted_at`` demotes first), NOT on slot index:
    slot-order tie-breaking systematically churned low-index slots
    under uniform traffic.  Remaining ties (same touch clock, same
    insert clock) fall back to slot order, which is then genuinely
    arbitrary.  Returned ``mask`` is False on padding rows (fewer than
    m valid).
    """
    idx = coldest_slots(state, m)
    mask = state.valid[idx]
    new_valid = state.valid.at[idx].set(
        jnp.where(mask, False, state.valid[idx]))
    dem = Demoted(keys=state.keys[idx], value_ids=state.value_ids[idx],
                  tenants=state.tenants[idx], mask=mask,
                  expires=state.expires_at[idx])
    return state._replace(valid=new_valid), dem


# ---------------------------------------------------------------------------
# warm tier
# ---------------------------------------------------------------------------

def quantize_rows(keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 per-row quantization of a (…, D) key panel.

    ``keys ≈ q8 * scale[..., None]`` with scale = amax/127; per-row
    reconstruction error is <= scale/2 per component, so a cosine score
    against a unit query is off by at most ``amax·sqrt(D)/254``
    (DESIGN.md §8).  Returns (q8 int8, scale float32).
    """
    amax = jnp.max(jnp.abs(keys), axis=-1)
    scale = jnp.maximum(amax, 1e-9) / 127.0
    q8 = jnp.clip(jnp.round(keys / scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return q8, scale.astype(jnp.float32)


def requantize(state: WarmState) -> WarmState:
    """Refresh ``keys_q``/``scales`` from ``keys`` — required after any
    bulk load that writes ``keys`` directly instead of `warm_append`."""
    q8, sc = quantize_rows(state.keys)
    return state._replace(keys_q=q8, scales=sc)


def init_warm(capacity: int, dim: int, n_clusters: int,
              bucket: int) -> WarmState:
    return WarmState(
        keys=jnp.zeros((capacity, dim), jnp.float32),
        valid=jnp.zeros((capacity,), bool),
        tenants=jnp.full((capacity,), -1, jnp.int32),
        value_ids=jnp.full((capacity,), -1, jnp.int32),
        write_seq=jnp.zeros((capacity,), jnp.int32),
        cursor=jnp.zeros((), jnp.int32),
        total=jnp.zeros((), jnp.int32),
        centroids=jnp.zeros((n_clusters, dim), jnp.float32),
        members=jnp.full((n_clusters, bucket), -1, jnp.int32),
        sizes=jnp.zeros((n_clusters,), jnp.int32),
        indexed_total=jnp.zeros((), jnp.int32),
        keys_q=jnp.zeros((capacity, dim), jnp.int8),
        scales=jnp.zeros((capacity,), jnp.float32),
        expires_at=jnp.full((capacity,), jnp.inf, jnp.float32),
    )


def init_warm_sharded(shards: int, capacity: int, dim: int, n_clusters: int,
                      bucket: int) -> WarmState:
    """Stacked warm tier: ``shards`` independent rings of ``capacity``
    rows and ``n_clusters`` local centroids each (leading axis laid
    over the mesh ``model`` axis by `cascade_query`)."""
    one = init_warm(capacity, dim, n_clusters, bucket)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (shards,) + x.shape), one)


def stack_warm(states) -> WarmState:
    """Stack per-shard WarmStates into the sharded (leading-axis) form."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def place_warm_sharded(warm: WarmState, mesh, axis: str = "model"
                       ) -> WarmState:
    """Commit a stacked warm state to the mesh: leading shard axis over
    ``axis``, everything else replicated.  Done once after init/bulk
    load — every later device op (vmapped append/rebuild, eviction,
    lookup) preserves the leading-axis sharding, so lookups read
    resident shards instead of resharding the corpus per call."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(*((axis,) + (None,) * (x.ndim - 1))))),
        warm)


def _dem_expires(dem: Demoted) -> jax.Array:
    """The demoted batch's expiry column, defaulting to +inf (no TTL)."""
    if dem.expires is None:
        return jnp.full(dem.mask.shape, jnp.inf, jnp.float32)
    return dem.expires.astype(jnp.float32)


def warm_append(state: WarmState, dem: Demoted) -> Tuple[WarmState, jax.Array]:
    """Ring-buffer append of a demoted batch (m <= warm capacity).

    Returns (state, evicted (m,) int32) — response ids of overwritten
    ring slots, -1 padding.  Appended rows are unindexed until the next
    rebuild; `warm_query`'s tail window keeps them reachable.  The int8
    panel (``keys_q``/``scales``) and the TTL column (``expires_at``)
    are maintained in the same update.
    """
    cap = state.keys.shape[0]
    offs = jnp.cumsum(dem.mask.astype(jnp.int32)) - 1              # (m,)
    pos = (state.cursor + offs) % cap
    dest = jnp.where(dem.mask, pos, cap)                           # cap=drop
    safe = jnp.clip(dest, 0, cap - 1)
    evicted = jnp.where(dem.mask & state.valid[safe],
                        state.value_ids[safe], -1).astype(jnp.int32)
    n = dem.mask.sum().astype(jnp.int32)
    seqs = state.total + 1 + offs
    kn = _unit(dem.keys.astype(jnp.float32))
    k8, sc = quantize_rows(kn)
    return state._replace(
        keys=state.keys.at[dest].set(kn, mode="drop"),
        valid=state.valid.at[dest].set(True, mode="drop"),
        tenants=state.tenants.at[dest].set(dem.tenants, mode="drop"),
        value_ids=state.value_ids.at[dest].set(dem.value_ids, mode="drop"),
        write_seq=state.write_seq.at[dest].set(seqs, mode="drop"),
        cursor=(state.cursor + n) % cap,
        total=state.total + n,
        keys_q=state.keys_q.at[dest].set(k8, mode="drop"),
        scales=state.scales.at[dest].set(sc, mode="drop"),
        expires_at=state.expires_at.at[dest].set(_dem_expires(dem),
                                                 mode="drop"),
    ), evicted


def warm_append_sharded(state: WarmState, dem: Demoted
                        ) -> Tuple[WarmState, jax.Array]:
    """Round-robin a demoted batch over the shard rings (row j of the
    batch lands on shard ``j % shards``, so every flush loads shards
    evenly).  ``m`` must divide by the shard count — `CacheService`
    snaps ``flush_size`` down to a shard multiple (min. one row per
    shard) to guarantee it.  Returns (state, evicted (m,) int32)."""
    shards = state.keys.shape[0]
    m = dem.keys.shape[0]
    if m % shards:
        raise ValueError(f"demoted batch {m} not divisible by "
                         f"{shards} shards")
    dem = dem._replace(expires=_dem_expires(dem))

    def split(x):
        return jnp.swapaxes(x.reshape((m // shards, shards) + x.shape[1:]),
                            0, 1)

    dem_s = Demoted(*(split(x) for x in dem))
    new, evicted = jax.vmap(warm_append)(state, dem_s)
    return new, evicted.reshape(-1)


def warm_rebuild(state: WarmState, iters: int = 8,
                 seed: int = 0) -> WarmState:
    """Re-cluster the warm corpus and refill the inverted lists
    (jittable: spherical k-means + the same static list fill as
    `build_ivf`).

    Double-buffering (DESIGN.md §7) runs this on a *snapshot* while
    serving keeps reading the published index; `warm_publish_index`
    then grafts the result onto the live state.
    """
    n_clusters, bucket = state.members.shape
    cent = ivf_lib.kmeans(state.keys, state.valid, n_clusters, iters, seed)
    members, sizes = ivf_lib.build_lists(state.keys, state.valid, cent,
                                         bucket)
    return state._replace(centroids=cent, members=members, sizes=sizes,
                          indexed_total=state.total)


def warm_rebuild_sharded(state: WarmState, iters: int = 8,
                         seed: int = 0) -> WarmState:
    """Per-shard re-cluster of the stacked warm tier: each shard runs
    its own spherical k-means over its local rows (vmapped, so one
    compile covers every shard)."""
    return jax.vmap(partial(warm_rebuild, iters=iters, seed=seed))(state)


def warm_publish_index(current: WarmState, shadow: WarmState) -> WarmState:
    """Atomically swap a shadow-built IVF into the live warm state.

    Only the index leaves move (centroids, inverted lists,
    ``indexed_total``); keys/valid/cursor/total stay the *current*
    ring, which may have advanced past the shadow's snapshot.  Because
    ``indexed_total`` becomes the snapshot's total, every row appended
    after the snapshot still satisfies ``write_seq > indexed_total``
    and is served by `warm_query`'s tail window, while ring slots
    overwritten post-snapshot are excluded from the (stale) inverted
    lists by the same epoch partition — so the swap can never create a
    recall dip or a duplicate candidate.

    Works unchanged on the stacked (sharded) form: the index leaves of
    every shard move in one ``_replace``, so the publish is
    shard-consistent — no lookup can ever observe shard A's new index
    next to shard B's old one (the swap happens between, never inside,
    jitted lookups).
    """
    return current._replace(centroids=shadow.centroids,
                            members=shadow.members, sizes=shadow.sizes,
                            indexed_total=shadow.indexed_total)


def publish_reembedded_keys(hot: HotState, warm: WarmState,
                            hot_keys: jax.Array, warm_keys: jax.Array
                            ) -> Tuple[HotState, WarmState]:
    """Atomically swap both tiers' key panels for re-embedded ones
    (DESIGN.md §11).

    The panels are full-capacity replacements built host-side by
    mapping each *currently valid* row's value id to its re-embedding
    under the candidate embedder; rows without a replacement (invalid
    slots, padding) must carry their current key so nothing else moves.
    Only ``keys`` (and the warm int8 mirror, requantized in the same
    update) change: ``valid``/``tenants``/``value_ids``/ring counters
    and the IVF leaves are untouched, so a row evicted while the shadow
    re-embed ran can never be resurrected by the publish, and the tail
    window / inverted-list partition is exactly as sound as before the
    swap.  Rows are re-normalized here so the cosine geometry is
    preserved no matter what the embedder emitted.  Works unchanged on
    the stacked (sharded) warm form — the leading shard axis broadcasts
    through.
    """
    hk = _unit(hot_keys.astype(jnp.float32))
    wk = _unit(warm_keys.astype(jnp.float32))
    q8, sc = quantize_rows(wk)
    return (hot._replace(keys=hk),
            warm._replace(keys=wk, keys_q=q8, scales=sc))


def warm_query(state: WarmState, q: jax.Array, q_tenants: jax.Array,
               k: int = 1, n_probe: int = 8, tail: int = 0
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """IVF probe + unindexed-tail scan, tenant-masked.

    Candidates are the members of the ``n_probe`` nearest clusters plus
    the last ``tail`` ring positions filtered to rows written after the
    last rebuild.  With tail >= flush_size * rebuild_every, every live
    row is reachable, so recall matches a full brute-force scan of the
    probed clusters.
    """
    qn = _unit(q.astype(jnp.float32))
    Q = qn.shape[0]
    cap = state.keys.shape[0]
    n_clusters, bucket = state.members.shape
    n_probe = min(n_probe, n_clusters)

    csims = cosine(qn, state.centroids)                            # (Q, K)
    _, probes = jax.lax.top_k(csims, n_probe)
    cand = state.members[probes].reshape(Q, n_probe * bucket)
    # partition candidates by write epoch so a slot overwritten after
    # the rebuild (stale member entry + tail member) never appears
    # twice: IVF side serves rows indexed at the last rebuild, the
    # tail serves rows written after it.
    is_tail = jnp.zeros(cand.shape, bool)
    if tail:
        tail_idx = (state.cursor - 1 - jnp.arange(tail, dtype=jnp.int32)) \
            % cap
        unindexed = state.write_seq[tail_idx] > state.indexed_total
        tail_cand = jnp.where(unindexed, tail_idx, -1)
        cand = jnp.concatenate(
            [cand, jnp.broadcast_to(tail_cand[None, :], (Q, tail))], axis=1)
        is_tail = jnp.concatenate(
            [is_tail, jnp.ones((Q, tail), bool)], axis=1)

    safe = jnp.clip(cand, 0, cap - 1)
    ok = (cand >= 0) & state.valid[safe] \
        & (state.tenants[safe] == q_tenants[:, None]) \
        & (is_tail | (state.write_seq[safe] <= state.indexed_total))
    scores = cosine(qn, state.keys[safe])
    scores = jnp.where(ok, scores, NEG)
    top_s, top_i = jax.lax.top_k(scores, k)
    rows = jnp.arange(Q)[:, None]
    slots = safe[rows, top_i]
    vids = jnp.where(top_s > NEG / 2, state.value_ids[slots], -1)
    return top_s, slots, vids


def warm_occupancy(state: WarmState) -> jax.Array:
    return jnp.mean(state.valid.astype(jnp.float32))


# ---------------------------------------------------------------------------
# cascade + tenant eviction
# ---------------------------------------------------------------------------

def cascade_lookup(hot: HotState, warm: WarmState, q: jax.Array,
                   q_tenants: jax.Array, thresholds: jax.Array,
                   k: int = 1, n_probe: int = 8,
                   tail: int = 0) -> CascadeResult:
    """One jitted lookup over both tiers.

    thresholds: (Q,) per-query operating points (host-resolved from the
    per-tenant policy table — a traced array, so mixed-tenant batches
    never retrace).
    """
    hs, hslots, hvids = hot_query(hot, q, q_tenants, k)
    ws, _, wvids = warm_query(warm, q, q_tenants, k, n_probe, tail)
    all_s = jnp.concatenate([hs, ws], axis=1)                      # (Q, 2k)
    all_v = jnp.concatenate([hvids, wvids], axis=1)
    s, i = jax.lax.top_k(all_s, k)
    rows = jnp.arange(s.shape[0])[:, None]
    vids = all_v[rows, i]
    hit = s[:, 0] >= thresholds
    hot_hit = hit & (i[:, 0] < k)
    return CascadeResult(scores=s, value_ids=vids, hot_slots=hslots[:, 0],
                         hot_hit=hot_hit, hit=hit)


def _cascade_ops(hot: HotState, warm: WarmState, qn, qt, thr, k, n_probe,
                 tail, use_kernel, quantized, warm_block_n=None):
    """Flat-array cascade through the kernel-package dispatch; returns
    the 6-tuple (scores, vids, warm_slots, hot_slots, hot_hit, hit)."""
    from repro.kernels.cascade_lookup import ops as _casc_ops
    return _casc_ops.cascade_lookup(
        qn, qt, thr, hot.keys, hot.valid, hot.tenants, hot.value_ids,
        warm.keys, warm.valid, warm.tenants, warm.value_ids,
        warm.write_seq, warm.centroids, warm.members,
        warm.cursor, warm.indexed_total, warm.keys_q, warm.scales,
        k=k, n_probe=n_probe, tail=tail, quantized=quantized,
        use_kernel=use_kernel, warm_block_n=warm_block_n)


def _rescore_exact(qn, keys, s, wslots):
    """Replace quantized-selected warm scores with exact fp32 cosines.

    Only the (Q, k) selected rows are gathered from the fp32 panel, so
    the exact pass costs O(Q·k·D) — the bulk scan stays int8.
    """
    safe = jnp.clip(wslots, 0, keys.shape[0] - 1)
    exact = cosine(qn, keys[safe])
    return jnp.where(wslots >= 0, exact, s)


def _shard_cascade(hot: HotState, warm: WarmState, qn, qt, thr, k, n_probe,
                   tail, use_kernel, quantized, shard_index,
                   warm_block_n=None):
    """One shard's candidates for the sharded cascade (DESIGN.md §8).

    The hot tier is replicated but *attributed to shard 0* (its valid
    mask is zeroed elsewhere), so the cross-shard merge never sees the
    same hot row twice.  Returns (scores (Q, k), vids (Q, k),
    is_hot (Q, k) int32, hot_slots (Q,)) — already exact-rescored when
    quantized, so the merge compares true cosines.
    """
    hot = hot._replace(valid=hot.valid & (shard_index == 0))
    s, vids, wslots, hslots, _, _ = _cascade_ops(
        hot, warm, qn, qt, thr, k, n_probe, tail, use_kernel, quantized,
        warm_block_n)
    if quantized:
        s = _rescore_exact(qn, warm.keys, s, wslots)
    is_hot = ((wslots < 0) & (s > NEG / 2)).astype(jnp.int32)
    return s, vids, is_hot, hslots


def _cascade_sharded_oracle(hot: HotState, swarm: WarmState, qn, qt, thr,
                            k, n_probe, tail, use_kernel, quantized,
                            warm_block_n=None) -> CascadeResult:
    """Single-device emulation of the sharded schedule — the bit-exact
    oracle the shard_map path is tested against.  Shard s's candidates
    occupy columns [s·k, (s+1)·k) of the merge panel, exactly like the
    tiled all-gather."""
    from repro.core.distrib import merge_stacked_topk
    shards = swarm.keys.shape[0]
    per = [_shard_cascade(hot,
                          jax.tree_util.tree_map(lambda x, i=i: x[i], swarm),
                          qn, qt, thr, k, n_probe, tail, use_kernel,
                          quantized, i, warm_block_n)
           for i in range(shards)]
    s, vids, is_hot = merge_stacked_topk(
        k, jnp.stack([p[0] for p in per]), jnp.stack([p[1] for p in per]),
        jnp.stack([p[2] for p in per]))
    hit = s[:, 0] >= thr
    hot_hit = hit & (is_hot[:, 0] != 0)
    return CascadeResult(scores=s, value_ids=vids, hot_slots=per[0][3],
                         hot_hit=hot_hit, hit=hit)


def _cascade_sharded(hot: HotState, swarm: WarmState, qn, qt, thr, k,
                     n_probe, tail, use_kernel, quantized, mesh,
                     axis, warm_block_n=None) -> CascadeResult:
    """shard_map execution of the sharded cascade: warm leaves split on
    their leading shard axis over ``axis``, hot/queries replicated, one
    (Q, k·shards) all-gather merge (`core.distrib.merge_local_topk`)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distrib import merge_local_topk

    def local(hot_, swarm_, qn_, qt_, thr_):
        i = jax.lax.axis_index(axis)
        warm_local = jax.tree_util.tree_map(lambda x: x[0], swarm_)
        s, vids, is_hot, hslots = _shard_cascade(
            hot_, warm_local, qn_, qt_, thr_, k, n_probe, tail,
            use_kernel, quantized, i, warm_block_n)
        sm, vm, hm = merge_local_topk(axis, k, s, vids, is_hot)
        hit = sm[:, 0] >= thr_
        hot_hit = hit & (hm[:, 0] != 0)
        # only shard 0 computed real hot slots; psum broadcasts them
        hslot0 = jax.lax.psum(jnp.where(i == 0, hslots, 0), axis)
        return sm, vm, hslot0, hot_hit, hit

    rep = P()
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: rep, hot),
                  jax.tree_util.tree_map(lambda _: P(axis), swarm),
                  rep, rep, rep),
        out_specs=(rep, rep, rep, rep, rep),
        check_vma=False)
    s, vids, hslots, hot_hit, hit = fn(hot, swarm, qn, qt, thr)
    return CascadeResult(scores=s, value_ids=vids, hot_slots=hslots,
                         hot_hit=hot_hit, hit=hit)


def cascade_query(hot: HotState, warm: WarmState, q: jax.Array,
                  q_tenants: jax.Array, thresholds: jax.Array,
                  k: int = 1, n_probe: int = 8, tail: int = 0,
                  fused: bool = False,
                  use_kernel: bool | None = None,
                  quantized: bool = False,
                  mesh=None, axis: str = "model",
                  warm_block_n: int | None = None) -> CascadeResult:
    """Cascade lookup with a selectable execution path.

    ``fused=False`` runs the original four-op XLA composition
    (`cascade_lookup`), the parity reference.  ``fused=True`` routes
    through `kernels/cascade_lookup` — one fused Pallas kernel on TPU
    (candidate panels stay in VMEM; the bucket-gather round-trip
    through HBM disappears) and the same four-op math as a single jnp
    oracle on CPU / interpret mode.  Both paths return bit-identical
    ``CascadeResult``s, including tenant masking, invalid slots and the
    tail window; ``use_kernel`` forces the Pallas path (interpret mode
    off-TPU) for parity tests.

    A stacked ``warm`` (leading shard axis, ``keys.ndim == 3``) selects
    the sharded schedule (DESIGN.md §8): per-shard local probe + local
    top-k (fused or four-op per shard), tiny (Q, k·shards) merge.  With
    ``mesh`` the shards execute under shard_map over ``axis``; without
    it the single-device oracle emulates the identical schedule (same
    results bit-for-bit).  ``tail`` is then the *per-shard* tail
    window.  ``quantized=True`` scans the warm panel from its int8
    form and re-scores the selected rows exactly (scores in the result
    are true fp32 cosines either way).  ``warm_block_n`` streams the
    warm panel through the fused kernel in blocks of that many rows
    (DESIGN.md §12) so a shard's warm slice may exceed its VMEM budget;
    results are bit-identical for every block count (and the flag is a
    no-op on the four-op / oracle paths).
    """
    sharded = warm.keys.ndim == 3
    if mesh is not None and not sharded:
        raise ValueError("cascade_query(mesh=...) needs the stacked "
                         "(sharded) WarmState; see init_warm_sharded")
    uk = use_kernel if fused else False
    if sharded:
        qn = _unit(q.astype(jnp.float32))
        qt = q_tenants.astype(jnp.int32)
        thr = jnp.asarray(thresholds, jnp.float32)
        if mesh is None:
            return _cascade_sharded_oracle(hot, warm, qn, qt, thr, k,
                                           n_probe, tail, uk, quantized,
                                           warm_block_n)
        return _cascade_sharded(hot, warm, qn, qt, thr, k, n_probe, tail,
                                uk, quantized, mesh, axis, warm_block_n)
    if not fused and not quantized:
        return cascade_lookup(hot, warm, q, q_tenants, thresholds, k=k,
                              n_probe=n_probe, tail=tail)
    qn = _unit(q.astype(jnp.float32))
    s, vids, wslots, hslots, hot_hit, hit = _cascade_ops(
        hot, warm, qn, q_tenants.astype(jnp.int32), thresholds, k,
        n_probe, tail, uk, quantized, warm_block_n)
    if quantized:
        # exact re-score may reorder the k selected candidates
        s = _rescore_exact(qn, warm.keys, s, wslots)
        s, idx = jax.lax.top_k(s, k)
        rows = jnp.arange(s.shape[0])[:, None]
        vids = vids[rows, idx]
        wslots = wslots[rows, idx]
        hit = s[:, 0] >= thresholds
        hot_hit = hit & (wslots[:, 0] < 0)
    return CascadeResult(scores=s, value_ids=vids, hot_slots=hslots,
                         hot_hit=hot_hit, hit=hit)


def evict_tenant(hot: HotState, warm: WarmState, tenant: jax.Array
                 ) -> Tuple[HotState, WarmState, jax.Array, jax.Array]:
    """Invalidate every row of one tenant in both tiers.

    Returns (hot, warm, hot_evicted, warm_evicted) where the evicted
    arrays are capacity-sized value-id lists (-1 padding) for host GC.
    """
    h_kill = hot.valid & (hot.tenants == tenant)
    w_kill = warm.valid & (warm.tenants == tenant)
    h_ev = jnp.where(h_kill, hot.value_ids, -1)
    w_ev = jnp.where(w_kill, warm.value_ids, -1)
    return (hot._replace(valid=hot.valid & ~h_kill),
            warm._replace(valid=warm.valid & ~w_kill), h_ev, w_ev)


# ---------------------------------------------------------------------------
# TTL / staleness (DESIGN.md §14)
# ---------------------------------------------------------------------------

def mask_expired(hot: HotState, warm: WarmState, now: jax.Array
                 ) -> Tuple[HotState, WarmState, jax.Array]:
    """Plan-time staleness mask: views of both tiers with every expired
    row's ``valid`` bit cleared, so the cascade (fused or four-op,
    sharded or not — the mask is elementwise and precedes the lookup)
    can never serve a stale entry.  The underlying state is untouched;
    `reap_expired` frees the rows on the maintenance tick.  Returns
    (hot_view, warm_view, n_masked) where ``n_masked`` counts rows that
    were valid but past their deadline.
    """
    now = jnp.asarray(now, jnp.float32)
    h_live = hot.expires_at > now
    w_live = warm.expires_at > now
    n = (hot.valid & ~h_live).sum() + (warm.valid & ~w_live).sum()
    return (hot._replace(valid=hot.valid & h_live),
            warm._replace(valid=warm.valid & w_live),
            n.astype(jnp.int32))


def reap_expired(hot: HotState, warm: WarmState, now: jax.Array
                 ) -> Tuple[HotState, WarmState, jax.Array, jax.Array]:
    """Free every expired row in both tiers (the maintenance-tick side
    of TTL, mirroring `evict_tenant`'s contract).

    Returns (hot, warm, hot_reaped, warm_reaped) where the reaped
    arrays are capacity-sized value-id lists (-1 padding) for host GC.
    Works unchanged on the stacked (sharded) warm form.
    """
    now = jnp.asarray(now, jnp.float32)
    h_kill = hot.valid & (hot.expires_at <= now)
    w_kill = warm.valid & (warm.expires_at <= now)
    h_ev = jnp.where(h_kill, hot.value_ids, -1)
    w_ev = jnp.where(w_kill, warm.value_ids, -1)
    return (hot._replace(valid=hot.valid & ~h_kill),
            warm._replace(valid=warm.valid & ~w_kill), h_ev, w_ev)


# ---------------------------------------------------------------------------
# multi-embedder ensemble: E stacked key panels over the shared tiers
# ---------------------------------------------------------------------------

class EnsembleState(NamedTuple):
    """E row-aligned key panels over the base tiers (DESIGN.md §13).

    The base ``HotState``/``WarmState`` keep every per-slot column
    (valid/tenant/value-id/write-seq), the ring counters and the IVF;
    panel 0 (the *pilot*) duplicates the base key panels so routing,
    rebuilds and the §11 refresh machinery stay single-embedder.  The
    extra panels are the same rows under the other embedders — row
    alignment is maintained by mirroring every slot decision of the
    base mutation (`ensemble_hot_insert_batch`, `ensemble_warm_append`)
    rather than by permuting, which `warm_rebuild` never does.  In the
    sharded form the warm leaves gain a *leading* shard axis
    ((S, E, cap, D) keys — detected via ``warm_keys.ndim == 4``) while
    ``hot_keys`` stays replicated, mirroring the base tiers.
    """
    hot_keys: jax.Array      # (E, Nh, D) float32 unit-norm
    warm_keys: jax.Array     # (E, Nw, D) float32 unit-norm
    warm_keys_q: jax.Array   # (E, Nw, D) int8 per-row symmetric quant
    warm_scales: jax.Array   # (E, Nw) float32 dequant scales


class EnsembleResult(NamedTuple):
    """`CascadeResult` plus the top-1 candidate's per-embedder cosines
    (``panel_scores``, -1.0 on rows with no candidate) — the feedback
    loop's training signal for per-tenant mixture weights."""
    scores: jax.Array        # (Q, k) fused best-of-tiers, desc
    value_ids: jax.Array     # (Q, k) -1 where no candidate
    hot_slots: jax.Array     # (Q,)
    hot_hit: jax.Array       # (Q,)
    hit: jax.Array           # (Q,)
    panel_scores: jax.Array  # (Q, E) unweighted per-panel cosines


def init_ensemble(n_embedders: int, hot: HotState,
                  warm: WarmState) -> EnsembleState:
    """Broadcast the base key panels into E aligned copies (a fresh
    service starts all-zero; a warm start seeds every panel with the
    pilot keys until each embedder's `publish_panel` lands)."""
    E = n_embedders
    hk = jnp.broadcast_to(hot.keys[None], (E,) + hot.keys.shape) + 0.0
    if warm.keys.ndim == 3:          # sharded: (S, cap, D) -> (S, E, cap, D)
        exp = lambda x: jnp.broadcast_to(
            x[:, None], (x.shape[0], E) + x.shape[1:]) + 0
    else:
        exp = lambda x: jnp.broadcast_to(x[None], (E,) + x.shape) + 0
    return EnsembleState(hot_keys=hk, warm_keys=exp(warm.keys),
                         warm_keys_q=exp(warm.keys_q),
                         warm_scales=exp(warm.scales).astype(jnp.float32))


def make_ensemble(hot_panels: jax.Array,
                  warm_panels: jax.Array) -> EnsembleState:
    """Build an `EnsembleState` from raw stacked panels ((E, Nh, D) /
    (E, Nw, D); sharded warm accepts (S, E, Nw, D)): unit-normalize and
    quantize — the bulk-load constructor for tests and benches."""
    hk = _unit(hot_panels.astype(jnp.float32))
    wk = _unit(warm_panels.astype(jnp.float32))
    q8, sc = quantize_rows(wk)
    return EnsembleState(hot_keys=hk, warm_keys=wk,
                         warm_keys_q=q8, warm_scales=sc)


def place_ensemble_sharded(ens: EnsembleState, mesh,
                           axis: str = "model") -> EnsembleState:
    """Commit a stacked ensemble to the mesh: warm leaves sharded on
    their leading axis, the hot panels replicated (mirrors
    `place_warm_sharded`)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    shard = lambda x: put(x, P(*((axis,) + (None,) * (x.ndim - 1))))
    return EnsembleState(hot_keys=put(ens.hot_keys, P()),
                         warm_keys=shard(ens.warm_keys),
                         warm_keys_q=shard(ens.warm_keys_q),
                         warm_scales=shard(ens.warm_scales))


def ensemble_hot_insert_batch(hot: HotState, ens: EnsembleState,
                              embs: jax.Array, value_ids: jax.Array,
                              tenants: jax.Array,
                              expires: jax.Array | None = None
                              ) -> Tuple[HotState, EnsembleState, jax.Array]:
    """`hot_insert_batch` with the E panels mirrored: embs is (B, E, D)
    (panel 0 = pilot).  Each step recomputes `_choose_slot` on the
    evolving hot state — the same deterministic choice `hot_insert`
    makes internally — and writes the full (E, D) row there, so the
    panels stay row-aligned with the base tier by construction.
    Returns (hot, ens, evicted (B,))."""
    if expires is None:
        expires = jnp.full(embs.shape[:1], jnp.inf, jnp.float32)

    def body(carry, xs):
        h, ehot = carry
        emb, vid, t, exp = xs                             # (E, D), (), ()
        slot = _choose_slot(h)
        h, ev = hot_insert(h, emb[0], vid, t, exp)
        en = _unit(emb.astype(jnp.float32))
        cur = ehot[:, slot]
        ehot = ehot.at[:, slot].set(jnp.where(vid < 0, cur, en))
        return (h, ehot), ev

    (hot, ehot), evicted = jax.lax.scan(
        body, (hot, ens.hot_keys), (embs, value_ids, tenants, expires))
    return hot, ens._replace(hot_keys=ehot), evicted


def ensemble_warm_append(ens: EnsembleState, warm: WarmState, dem: Demoted,
                         panel_keys: jax.Array) -> EnsembleState:
    """Mirror of `warm_append` for the stacked panels: the identical
    ring arithmetic from the *pre-append* warm state, applied to the
    (E, m, D) panel rows of the demoted batch (gathered by the caller
    via `coldest_slots` before the demote).  Call `warm_append` on the
    base state with the same ``dem`` alongside."""
    cap = warm.keys.shape[0]
    offs = jnp.cumsum(dem.mask.astype(jnp.int32)) - 1
    pos = (warm.cursor + offs) % cap
    dest = jnp.where(dem.mask, pos, cap)                  # cap = drop
    kn = _unit(panel_keys.astype(jnp.float32))            # (E, m, D)
    k8, sc = quantize_rows(kn)
    set_rows = jax.vmap(lambda p, v: p.at[dest].set(v, mode="drop"))
    return ens._replace(
        warm_keys=set_rows(ens.warm_keys, kn),
        warm_keys_q=set_rows(ens.warm_keys_q, k8),
        warm_scales=set_rows(ens.warm_scales, sc))


def ensemble_warm_append_sharded(ens: EnsembleState, warm: WarmState,
                                 dem: Demoted, panel_keys: jax.Array
                                 ) -> EnsembleState:
    """`warm_append_sharded`'s round-robin, mirrored onto the stacked
    panels: batch row j lands on shard ``j % shards`` exactly as the
    base append routes it, so per-shard row alignment is preserved."""
    shards = warm.keys.shape[0]
    m = dem.keys.shape[0]
    if m % shards:
        raise ValueError(f"demoted batch {m} not divisible by "
                         f"{shards} shards")
    dem = dem._replace(expires=_dem_expires(dem))

    def split(x):
        return jnp.swapaxes(x.reshape((m // shards, shards) + x.shape[1:]),
                            0, 1)

    dem_s = Demoted(*(split(x) for x in dem))
    pk_s = jnp.transpose(
        panel_keys.reshape(panel_keys.shape[0], m // shards, shards, -1),
        (2, 0, 1, 3))                                     # (S, E, m/S, D)

    def one(wk, wq, wsc, warm_i, dem_i, pk_i):
        sub = EnsembleState(hot_keys=ens.hot_keys, warm_keys=wk,
                            warm_keys_q=wq, warm_scales=wsc)
        sub = ensemble_warm_append(sub, warm_i, dem_i, pk_i)
        return sub.warm_keys, sub.warm_keys_q, sub.warm_scales

    wk, wq, wsc = jax.vmap(one)(ens.warm_keys, ens.warm_keys_q,
                                ens.warm_scales, warm, dem_s, pk_s)
    return ens._replace(warm_keys=wk, warm_keys_q=wq, warm_scales=wsc)


def publish_panel(ens: EnsembleState, e: int, hot_keys: jax.Array,
                  warm_keys: jax.Array) -> EnsembleState:
    """Atomically swap ONE embedder's key panels — the E-panel
    generalization of `publish_reembedded_keys` (DESIGN.md §13): with
    the panel's mixture weight at w, this IS A/B shadow serving of a
    candidate embedder during a §11 hot-swap.  Rows re-normalize and
    the int8 mirror requantizes in the same update; per-slot metadata
    and the pilot-built IVF are untouched.  Publishing panel 0 must go
    through `publish_reembedded_keys` on the base tiers as well — the
    pilot panel is a duplicate of ``hot.keys``/``warm.keys``."""
    hk = _unit(hot_keys.astype(jnp.float32))
    wk = _unit(warm_keys.astype(jnp.float32))
    q8, sc = quantize_rows(wk)
    if ens.warm_keys.ndim == 4:      # sharded warm leaves: (S, E, cap, D)
        return ens._replace(
            hot_keys=ens.hot_keys.at[e].set(hk),
            warm_keys=ens.warm_keys.at[:, e].set(wk),
            warm_keys_q=ens.warm_keys_q.at[:, e].set(q8),
            warm_scales=ens.warm_scales.at[:, e].set(sc))
    return ens._replace(
        hot_keys=ens.hot_keys.at[e].set(hk),
        warm_keys=ens.warm_keys.at[e].set(wk),
        warm_keys_q=ens.warm_keys_q.at[e].set(q8),
        warm_scales=ens.warm_scales.at[e].set(sc))


def _ensemble_ops(hot: HotState, warm: WarmState, ens: EnsembleState,
                  qe, w, qt, thr, k, n_probe, tail, use_kernel, quantized,
                  warm_block_n=None):
    """E-panel cascade through the kernel-package dispatch; returns the
    6-tuple (scores, vids, warm_slots, hot_slots, hot_hit, hit)."""
    from repro.kernels.cascade_lookup import ops as _casc_ops
    return _casc_ops.ensemble_lookup(
        qe, w, qt, thr, ens.hot_keys, hot.valid, hot.tenants, hot.value_ids,
        ens.warm_keys, warm.valid, warm.tenants, warm.value_ids,
        warm.write_seq, warm.centroids, warm.members, warm.cursor,
        warm.indexed_total, ens.warm_keys_q, ens.warm_scales,
        k=k, n_probe=n_probe, tail=tail, quantized=quantized,
        use_kernel=use_kernel, warm_block_n=warm_block_n)


def _rescore_exact_fused(qe, w, warm_panels, s, wslots):
    """Exact fp32 re-score of quantized-selected warm winners, per
    panel, re-fused with the same stacked contraction the scan used —
    O(Q·k·E·D) on the few selected rows (DESIGN.md §13)."""
    E = qe.shape[0]
    safe = jnp.clip(wslots, 0, warm_panels.shape[1] - 1)
    exact = fuse([cosine(qe[e], warm_panels[e][safe]) for e in range(E)],
                 w)
    return jnp.where(wslots >= 0, exact, s)


def _top1_panel_scores(qe, hot_panels, warm_winner_keys, wslot0, hslots,
                       has):
    """Per-embedder cosines of each query's merged top-1 candidate.

    ``warm_winner_keys`` is the (Q, E, D) gather of the winning warm
    rows (caller-side, since the sharded path gathers across shards);
    hot winners resolve through ``hslots`` — every hot candidate in a
    merge comes from the replicated hot tier, whose best row is always
    the hot top-1, so the slot is known whenever the winner is hot.
    """
    hsafe = jnp.clip(hslots, 0, hot_panels.shape[1] - 1)
    hkeys = jnp.swapaxes(hot_panels[:, hsafe], 0, 1)      # (Q, E, D)
    keys = jnp.where((wslot0 >= 0)[:, None, None], warm_winner_keys, hkeys)
    ps = jnp.einsum("eqd,qed->qe", qe, keys)
    return jnp.where(has[:, None], ps, -1.0)


def _shard_ensemble(hot: HotState, warm: WarmState, ens: EnsembleState,
                    qe, w, qt, thr, k, n_probe, tail, use_kernel, quantized,
                    shard_index, warm_block_n=None):
    """One shard's fused-ensemble candidates (mirrors `_shard_cascade`:
    hot attributed to shard 0, exact fused re-score before the merge).
    Returns (scores, vids, is_hot, hot_slots, warm_slots)."""
    hot = hot._replace(valid=hot.valid & (shard_index == 0))
    s, vids, wslots, hslots, _, _ = _ensemble_ops(
        hot, warm, ens, qe, w, qt, thr, k, n_probe, tail, use_kernel,
        quantized, warm_block_n)
    if quantized:
        s = _rescore_exact_fused(qe, w, ens.warm_keys, s, wslots)
    is_hot = ((wslots < 0) & (s > NEG / 2)).astype(jnp.int32)
    return s, vids, is_hot, hslots, wslots


def _ens_shard(ens: EnsembleState, i) -> EnsembleState:
    """Extract one shard's panel view ((S, E, …) -> (E, …)); hot panels
    are replicated, so only the warm leaves index."""
    return ens._replace(warm_keys=ens.warm_keys[i],
                        warm_keys_q=ens.warm_keys_q[i],
                        warm_scales=ens.warm_scales[i])


def _ensemble_sharded_oracle(hot, swarm, ens, qe, w, qt, thr, k, n_probe,
                             tail, use_kernel, quantized,
                             warm_block_n=None) -> EnsembleResult:
    """Single-device emulation of the sharded fused-ensemble schedule —
    the bit-exact oracle the shard_map path is tested against."""
    from repro.core.distrib import merge_stacked_topk
    shards = swarm.keys.shape[0]
    per = [_shard_ensemble(hot,
                           jax.tree_util.tree_map(lambda x, i=i: x[i], swarm),
                           _ens_shard(ens, i), qe, w, qt, thr, k, n_probe,
                           tail, use_kernel, quantized, i, warm_block_n)
           for i in range(shards)]
    Q = qe.shape[1]
    shard_cols = [jnp.full((Q, k), i, jnp.int32) for i in range(shards)]
    s, vids, is_hot, wslot, wshard = merge_stacked_topk(
        k, jnp.stack([p[0] for p in per]), jnp.stack([p[1] for p in per]),
        jnp.stack([p[2] for p in per]), jnp.stack([p[4] for p in per]),
        jnp.stack(shard_cols))
    hit = s[:, 0] >= thr
    hot_hit = hit & (is_hot[:, 0] != 0)
    hslots = per[0][3]
    cap = ens.warm_keys.shape[2]
    wsafe = jnp.clip(wslot[:, 0], 0, cap - 1)
    ssafe = jnp.clip(wshard[:, 0], 0, shards - 1)
    wwin = ens.warm_keys[ssafe, :, wsafe]                 # (Q, E, D)
    ps = _top1_panel_scores(qe, ens.hot_keys, wwin, wslot[:, 0], hslots,
                            vids[:, 0] >= 0)
    return EnsembleResult(scores=s, value_ids=vids, hot_slots=hslots,
                          hot_hit=hot_hit, hit=hit, panel_scores=ps)


def _ensemble_sharded(hot, swarm, ens, qe, w, qt, thr, k, n_probe, tail,
                      use_kernel, quantized, mesh, axis,
                      warm_block_n=None) -> EnsembleResult:
    """shard_map execution of the sharded fused ensemble: warm tiers
    and panel leaves split on their leading shard axis, hot panels and
    queries replicated, one (Q, k·shards) merge carrying (vid, is_hot,
    warm-slot, shard) payloads so the winner's panel keys can be
    gathered after the merge."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distrib import merge_local_topk

    def local(hot_, swarm_, ewk_, ewq_, ewsc_, ehot_, qe_, w_, qt_, thr_):
        i = jax.lax.axis_index(axis)
        warm_local = jax.tree_util.tree_map(lambda x: x[0], swarm_)
        ens_local = EnsembleState(hot_keys=ehot_, warm_keys=ewk_[0],
                                  warm_keys_q=ewq_[0], warm_scales=ewsc_[0])
        s, vids, is_hot, hslots, wslots = _shard_ensemble(
            hot_, warm_local, ens_local, qe_, w_, qt_, thr_, k, n_probe,
            tail, use_kernel, quantized, i, warm_block_n)
        shard_col = jnp.full(s.shape, i, jnp.int32)
        sm, vm, hm, wm, cm = merge_local_topk(axis, k, s, vids, is_hot,
                                              wslots, shard_col)
        hit = sm[:, 0] >= thr_
        hot_hit = hit & (hm[:, 0] != 0)
        # only shard 0 computed real hot slots; psum broadcasts them
        hslot0 = jax.lax.psum(jnp.where(i == 0, hslots, 0), axis)
        return sm, vm, hslot0, hot_hit, hit, wm, cm

    rep = P()
    shard = lambda x: P(*((axis,) + (None,) * (x.ndim - 1)))
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: rep, hot),
                  jax.tree_util.tree_map(lambda _: P(axis), swarm),
                  shard(ens.warm_keys), shard(ens.warm_keys_q),
                  shard(ens.warm_scales), rep, rep, rep, rep, rep),
        out_specs=(rep,) * 7,
        check_vma=False)
    s, vids, hslots, hot_hit, hit, wslot, wshard = fn(
        hot, swarm, ens.warm_keys, ens.warm_keys_q, ens.warm_scales,
        ens.hot_keys, qe, w, qt, thr)
    shards = swarm.keys.shape[0]
    cap = ens.warm_keys.shape[2]
    wsafe = jnp.clip(wslot[:, 0], 0, cap - 1)
    ssafe = jnp.clip(wshard[:, 0], 0, shards - 1)
    wwin = ens.warm_keys[ssafe, :, wsafe]                 # (Q, E, D)
    ps = _top1_panel_scores(qe, ens.hot_keys, wwin, wslot[:, 0], hslots,
                            vids[:, 0] >= 0)
    return EnsembleResult(scores=s, value_ids=vids, hot_slots=hslots,
                          hot_hit=hot_hit, hit=hit, panel_scores=ps)


def ensemble_cascade_query(hot: HotState, warm: WarmState,
                           ens: EnsembleState, q: jax.Array,
                           weights: jax.Array, q_tenants: jax.Array,
                           thresholds: jax.Array, k: int = 1,
                           n_probe: int = 8, tail: int = 0,
                           fused: bool = False,
                           use_kernel: bool | None = None,
                           quantized: bool = False, mesh=None,
                           axis: str = "model",
                           warm_block_n: int | None = None
                           ) -> EnsembleResult:
    """Fused multi-embedder cascade lookup (DESIGN.md §13).

    q: (Q, E, D) — one embedding per embedder per query, panel 0 the
    pilot; weights: (Q, E) per-query mixture weights (host-resolved
    from the per-tenant policy table, like thresholds).  Execution
    paths, sharding detection, quantization semantics and
    ``warm_block_n`` all mirror `cascade_query`; scores everywhere are
    the weighted fused cosine, and routing runs once on the pilot
    panel against the base tier's (pilot-built) IVF.  The result adds
    ``panel_scores`` — the top-1 candidate's unweighted per-embedder
    cosines, which `feedback` records to learn the weights.
    """
    sharded = ens.warm_keys.ndim == 4
    if sharded != (warm.keys.ndim == 3):
        raise ValueError("ensemble/warm sharding mismatch: warm keys "
                         f"ndim {warm.keys.ndim}, ensemble warm ndim "
                         f"{ens.warm_keys.ndim}")
    if mesh is not None and not sharded:
        raise ValueError("ensemble_cascade_query(mesh=...) needs the "
                         "stacked (sharded) panels; see "
                         "place_ensemble_sharded")
    qe = jnp.swapaxes(_unit(q.astype(jnp.float32)), 0, 1)  # (E, Q, D)
    qt = q_tenants.astype(jnp.int32)
    thr = jnp.asarray(thresholds, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    uk = use_kernel if fused else False
    if sharded:
        if mesh is None:
            return _ensemble_sharded_oracle(hot, warm, ens, qe, w, qt, thr,
                                            k, n_probe, tail, uk, quantized,
                                            warm_block_n)
        return _ensemble_sharded(hot, warm, ens, qe, w, qt, thr, k, n_probe,
                                 tail, uk, quantized, mesh, axis,
                                 warm_block_n)
    s, vids, wslots, hslots, hot_hit, hit = _ensemble_ops(
        hot, warm, ens, qe, w, qt, thr, k, n_probe, tail, uk, quantized,
        warm_block_n)
    if quantized:
        # exact fused re-score may reorder the k selected candidates
        s = _rescore_exact_fused(qe, w, ens.warm_keys, s, wslots)
        s, idx = jax.lax.top_k(s, k)
        rows = jnp.arange(s.shape[0])[:, None]
        vids = vids[rows, idx]
        wslots = wslots[rows, idx]
        hit = s[:, 0] >= thr
        hot_hit = hit & (wslots[:, 0] < 0)
    cap = ens.warm_keys.shape[1]
    wsafe = jnp.clip(wslots[:, 0], 0, cap - 1)
    wwin = jnp.swapaxes(ens.warm_keys[:, wsafe], 0, 1)    # (Q, E, D)
    ps = _top1_panel_scores(qe, ens.hot_keys, wwin, wslots[:, 0], hslots,
                            vids[:, 0] >= 0)
    return EnsembleResult(scores=s, value_ids=vids, hot_slots=hslots,
                          hot_hit=hot_hit, hit=hit, panel_scores=ps)
