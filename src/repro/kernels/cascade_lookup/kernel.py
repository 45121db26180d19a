"""Pallas TPU kernel: the tiered cache's cascade lookup, fused.

The unfused cascade (DESIGN.md §3) is four XLA ops — hot-tier matmul,
warm centroid matmul, IVF bucket gather, masked top-k — and the gather
round-trips its (Q × n_probe·bucket × D) candidate panel through HBM,
which dominates warm-tier latency.  This kernel extends
`kernels/cosine_topk`'s streaming running-top-k to the whole cascade in
one `pallas_call`:

  * grid steps 0..nh-1 stream the HOT tier through VMEM in
    (BLOCK_N × D) tiles, carrying a tenant-masked running top-k in
    scratch exactly like `cosine_topk`;
  * grid steps nh..nh+nw-1 stream the WARM key panel through VMEM in
    (WARM_BLOCK_N × D) tiles.  Each step recomputes the (tiny) probe
    selection — centroid matmul + masked-argmax rounds over the
    VMEM-resident centroids — then scores the IVF candidates and
    unindexed-tail candidates *that live in the current block* via
    in-kernel index arithmetic over the inverted lists, merging them
    into a warm running top-k carried in scratch.  Neither the
    (Q × candidates) score matrix nor any gathered key panel ever
    materializes in HBM, and no step holds more than one key block
    plus one (Q, bucket, D) gather panel in VMEM;
  * the final grid step merges the two accumulators (best-of-tiers,
    hot candidates first so ties stay hot) and maps slots to value ids.

Candidate ordering matches `jax.lax.top_k` tie-breaking (lowest panel
index wins) exactly: the hot stream visits slots in index order with
the accumulator concatenated first, and the warm accumulator carries
each candidate's *flat panel position* (probe-major, tail last — the
position it occupies in the oracle's single gathered panel) as an
explicit tie key, so streaming the blocks in any order is
bit-compatible with the served jnp cascade — `ref.py` — including
tenant masking, invalid slots and the tail window.

``quantized=True`` swaps the streamed warm blocks for their int8
symmetric per-row quantization (``warm_keys`` arrives as int8 plus a
per-row fp32 scale vector, both streamed blockwise): each (Q, bucket)
panel is dequantized only transiently, scores accumulate in fp32, and
both VMEM residency and the HBM→VMEM stream for the warm corpus shrink
4x (DESIGN.md §8).  The returned ``warm_slots`` let the caller re-score
the few selected rows exactly from the fp32 panel at merge time.

VMEM budget: only the centroids, inverted lists and the per-slot warm
metadata columns ((cap,) int32 each) are held whole; the key panels —
the VMEM hog — stream.  ``warm_block_n`` therefore bounds residency at
``warm_block_n·D`` key bytes regardless of warm capacity: a shard's
warm slice may exceed the old single-block design size (DESIGN.md §12)
at the cost of one extra probe-panel pass per additional block.  Valid
masks travel as int32 (1, N) rows and the hit flags return as int32
(bool VMEM refs are a Mosaic lowering hazard); every top-k is the
gather-free `cosine_topk.select_topk`, and scores are `ref.cosine` /
`ref.fuse`, so interpret mode is bit-equal with the oracle.

Mosaic refuses this kernel for a TPU: the IVF candidate panels are
data-dependent VMEM gathers (``mem[probes]``, ``wkb[local]`` and the
metadata columns at ``gsafe``), which it cannot lower.  `interpret=True`
runs the same dataflow as XLA ops on the CPU, where the tests check it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cascade_lookup.ref import cosine, fuse
from repro.kernels.cosine_topk.kernel import (
    DEFAULT_BLOCK_N, NEG_INF, POS_PAD, select_topk,
)


def _kernel(*refs, ensemble: bool, k: int, block_n: int, n_hot: int,
            n_hot_blocks: int, warm_block_n: int, n_warm: int,
            n_probe: int, tail: int, quantized: bool):
    """One grid sweep: hot blocks, then warm blocks, then the merge.

    With ``ensemble`` (DESIGN.md §13) every key-panel stream carries E
    stacked panels and every score is the weighted fused similarity
    ``sum_e w[q, e] · cos(q_e, key_e)`` (`ref.fuse`, the oracle's own
    primitive); routing (probe selection and the IVF index arithmetic)
    runs once, on the unweighted pilot panel, so the candidate index
    stream and all masks are shared across panels."""
    if ensemble:
        q_ref, w_ref, *refs = refs
    else:
        q_ref, *refs = refs
    (qt_ref, thr_ref, hk_ref, hv_ref, ht_ref, hvid_ref, wk_ref, wscale_ref,
     wv_ref, wt_ref, wvid_ref, wseq_ref, cent_ref, mem_ref, meta_ref,
     out_s_ref, out_v_ref, out_wslot_ref, out_hslot_ref, out_flag_ref,
     acc_s, acc_i, acc_v, wacc_s, wacc_p, wacc_i, wacc_v) = refs
    j = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.full_like(acc_s, NEG_INF)
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_v[...] = jnp.zeros_like(acc_v)
        wacc_s[...] = jnp.full_like(wacc_s, NEG_INF)
        wacc_p[...] = jnp.full_like(wacc_p, POS_PAD)
        wacc_i[...] = jnp.zeros_like(wacc_i)
        wacc_v[...] = jnp.zeros_like(wacc_v)

    q = q_ref[...].astype(jnp.float32)                 # (Q, D) | (E, Q, D)
    qt = qt_ref[...]                                   # (Q, 1)
    if ensemble:
        w = w_ref[...].astype(jnp.float32)             # (Q, E)
        E = q.shape[0]
        route_q = q[0]

        def scores(panel):                             # (E, N, D)|(E, Q, N, D)
            return fuse([cosine(q[e], panel[e]) for e in range(E)], w)
    else:
        route_q = q

        def scores(panel):
            return cosine(q, panel)
    Q = route_q.shape[0]

    # ---- hot tier: streamed block, tenant-masked running top-k ------
    @pl.when(j < n_hot_blocks)
    def _hot():
        s = scores(hk_ref[...].astype(jnp.float32))    # (Q, BN)
        col = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (hv_ref[...] != 0) & (ht_ref[...] == qt) & (col < n_hot)
        s = jnp.where(ok, s, NEG_INF)
        new_s, (new_i, new_v) = select_topk(
            k, [(acc_s[...], acc_i[...], acc_v[...]),
                (s, col, jnp.broadcast_to(hvid_ref[...], s.shape))])
        acc_s[...] = new_s
        acc_i[...] = new_i
        acc_v[...] = new_v

    # ---- warm tier: streamed block, position-keyed running top-k ----
    @pl.when(j >= n_hot_blocks)
    def _warm():
        b = j - n_hot_blocks
        base = b * warm_block_n
        bucket = mem_ref.shape[1]
        cursor = meta_ref[0]
        indexed_total = meta_ref[1]
        wv = wv_ref[0] != 0                            # (cap,) whole
        wt = wt_ref[0]
        wvid = wvid_ref[0]
        wseq = wseq_ref[0]
        wkb = wk_ref[...]          # (WB, D) | (E, WB, D); int8 if quantized
        if quantized:
            # int8 warm block stays int8-resident: dequantize one
            # (Q, B, D) gather at a time, fp32 accumulation
            wsc = wscale_ref[...]                      # (1, WB) | (E, WB)

            def panel_scores(local):
                if ensemble:
                    return fuse([cosine(q[e], wkb[e][local].astype(
                        jnp.float32)) * wsc[e][local] for e in range(E)], w)
                return cosine(q, wkb[local].astype(jnp.float32)) \
                    * wsc[0][local]
        else:
            wkb = wkb.astype(jnp.float32)

            def panel_scores(local):
                if ensemble:
                    return scores(wkb[:, local])
                return scores(wkb[local])

        # probe selection: centroid scores + n_probe rounds — recomputed
        # per block from the VMEM-resident centroids (tiny,
        # deterministic: every block sees identical probes)
        csims = cosine(route_q, cent_ref[...].astype(jnp.float32))  # (Q, K)
        pcol = jax.lax.broadcasted_iota(jnp.int32, csims.shape, 1)
        _, (probes,) = select_topk(n_probe, [(csims, pcol)])  # (Q, n_probe)

        # IVF gather: one (Q, bucket) candidate panel per probe, index
        # arithmetic over the inverted lists, restricted to candidates
        # whose row lives in this block — each live candidate is scored
        # exactly once across the sweep, in its own block, tagged with
        # its flat panel position so merge order is block-invariant
        mem = mem_ref[...]                             # (K, bucket)
        acc = (wacc_s[...], wacc_p[...], wacc_i[...], wacc_v[...])

        def merge(acc, cand, fpos0, extra_ok):
            local = cand - base
            inblk = (cand >= 0) & (local >= 0) & (local < warm_block_n)
            gsafe = jnp.clip(cand, 0, n_warm - 1)
            sc = panel_scores(jnp.clip(local, 0, warm_block_n - 1))
            ok = inblk & wv[gsafe] & (wt[gsafe] == qt) & extra_ok(gsafe)
            sc = jnp.where(ok, sc, NEG_INF)
            fpos = fpos0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            fpos = jnp.where(ok, fpos, POS_PAD)
            s, pay = select_topk(k, [acc, (sc, fpos, gsafe, wvid[gsafe])],
                                 tie=True)
            return (s, *pay)

        for p in range(n_probe):
            acc = merge(acc, mem[probes[:, p]], p * bucket,
                        lambda g: wseq[g] <= indexed_total)

        # unindexed-tail scan: last `tail` ring writes, newest first
        if tail:
            offs = jax.lax.broadcasted_iota(jnp.int32, (1, tail), 1)
            pos = (cursor - 1 - offs) % n_warm         # (1, tail)
            unindexed = wseq[pos] > indexed_total
            tcand = jnp.broadcast_to(jnp.where(unindexed, pos, -1),
                                     (Q, tail))
            acc = merge(acc, tcand, n_probe * bucket, lambda g: True)
        wacc_s[...], wacc_p[...], wacc_i[...], wacc_v[...] = acc

    # ---- best-of-tiers merge: once, after the last warm block -------
    @pl.when(j == nb - 1)
    def _finish():
        hs, ws = acc_s[...], wacc_s[...]
        live_h, live_w = hs > NEG_INF / 2, ws > NEG_INF / 2
        kcol = jax.lax.broadcasted_iota(jnp.int32, hs.shape, 1)
        out_s, (out_p, out_v, out_w) = select_topk(k, [
            (hs, kcol, jnp.where(live_h, acc_v[...], -1),
             jnp.full(hs.shape, -1, jnp.int32)),
            (ws, kcol + k, jnp.where(live_w, wacc_v[...], -1),
             jnp.where(live_w, wacc_i[...], -1))])
        out_s_ref[...] = out_s
        out_v_ref[...] = out_v
        out_wslot_ref[...] = out_w
        out_hslot_ref[...] = acc_i[...][:, :1]
        hit = out_s[:, :1] >= thr_ref[...]
        hot_hit = hit & (out_p[:, :1] < k)
        fcol = jax.lax.broadcasted_iota(jnp.int32, (Q, 2), 1)
        out_flag_ref[...] = jnp.where(fcol == 0, hit, hot_hit).astype(
            jnp.int32)


def _fused_call(q, weights, q_tenants, thresholds, hot_keys, hot_valid,
                hot_tenants, hot_value_ids, warm_keys, warm_valid,
                warm_tenants, warm_value_ids, warm_write_seq, centroids,
                members, cursor, indexed_total, warm_keys_q, warm_scales,
                k, n_probe, tail, quantized, block_n, warm_block_n,
                interpret):
    """Shared pallas_call of both entry points; ``weights is None``
    selects the single-embedder kernel (q (Q, D), panels (N, D)), else
    the E-panel ensemble (q (E, Q, D), panels (E, N, D))."""
    ensemble = weights is not None
    lead = q.shape[:1] if ensemble else ()            # (E,) | ()
    Q, D = q.shape[-2:]
    n_hot = hot_keys.shape[-2]
    n_clusters = centroids.shape[0]
    n_probe = min(n_probe, n_clusters)
    cap = warm_keys.shape[-2]

    if quantized:
        wk_in = warm_keys_q.astype(jnp.int8)
        wscale_in = warm_scales.astype(jnp.float32)
    else:
        wk_in = warm_keys.astype(jnp.float32)
        wscale_in = jnp.zeros(lead + (cap,), jnp.float32)  # unread
    if not ensemble:
        wscale_in = wscale_in[None, :]                 # (1, cap) row

    bn = min(block_n, n_hot)
    n_blocks = -(-n_hot // bn)
    pad = n_blocks * bn - n_hot
    # bool VMEM refs are a Mosaic lowering hazard, and 1-D blocks miss
    # its tiling: per-slot columns travel as int32 (1, N) rows
    row = lambda x: jnp.asarray(x).astype(jnp.int32)[None, :]
    hot_valid, hot_tenants, hot_value_ids = (
        row(hot_valid), row(hot_tenants), row(hot_value_ids))
    if pad:
        hot_keys = jnp.pad(hot_keys, ((0, 0),) * len(lead)
                           + ((0, pad), (0, 0)))
        hot_valid = jnp.pad(hot_valid, ((0, 0), (0, pad)))
        hot_tenants = jnp.pad(hot_tenants, ((0, 0), (0, pad)),
                              constant_values=-1)
        hot_value_ids = jnp.pad(hot_value_ids, ((0, 0), (0, pad)),
                                constant_values=-1)

    wb = min(warm_block_n or cap, cap)
    n_wblocks = -(-cap // wb)
    wpad = n_wblocks * wb - cap
    if wpad:
        # only the streamed panels pad (their BlockSpec tiles the padded
        # extent); per-slot metadata stays (1, cap) — no candidate id
        # ever reaches the pad rows, so they are dead weight, never read
        wk_in = jnp.pad(wk_in, ((0, 0),) * len(lead)
                        + ((0, wpad), (0, 0)))
        wscale_in = jnp.pad(wscale_in, ((0, 0), (0, wpad)))
    meta = jnp.stack([jnp.asarray(cursor, jnp.int32),
                      jnp.asarray(indexed_total, jnp.int32)])

    bucket = members.shape[1]
    grid = (n_blocks + n_wblocks,)
    whole = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    # clamped index maps: hot tiles only advance through the hot steps,
    # warm tiles only through the warm steps — a revisited index fetches
    # nothing new, so neither stream pays for the other's phase
    hj = lambda j: jnp.minimum(j, n_blocks - 1)
    wj = lambda j: jnp.maximum(j - n_blocks, 0)
    zl = (0,) * len(lead)
    in_specs = [whole(q.shape)]
    args = [q.astype(jnp.float32)]
    if ensemble:
        in_specs.append(whole(weights.shape))
        args.append(weights.astype(jnp.float32))
    in_specs += [
        whole((Q, 1)),                                    # q_tenants
        whole((Q, 1)),                                    # thresholds
        pl.BlockSpec(lead + (bn, D), lambda j: zl + (hj(j), 0)),  # hot keys
        pl.BlockSpec((1, bn), lambda j: (0, hj(j))),      # hot valid
        pl.BlockSpec((1, bn), lambda j: (0, hj(j))),      # hot tenants
        pl.BlockSpec((1, bn), lambda j: (0, hj(j))),      # hot value ids
        pl.BlockSpec(lead + (wb, D), lambda j: zl + (wj(j), 0)),  # warm keys
        pl.BlockSpec(wscale_in.shape[:1] + (wb,),
                     lambda j: (0, wj(j))),               # warm row scales
        whole((1, cap)),                                  # warm valid
        whole((1, cap)),                                  # warm tenants
        whole((1, cap)),                                  # warm value ids
        whole((1, cap)),                                  # warm write seq
        whole((n_clusters, D)),                           # centroids
        whole((n_clusters, bucket)),                      # inverted lists
        pl.BlockSpec(memory_space=pltpu.SMEM),            # cursor/indexed
    ]
    args += [
        jnp.asarray(q_tenants, jnp.int32)[:, None],
        jnp.asarray(thresholds, jnp.float32)[:, None],
        hot_keys.astype(jnp.float32), hot_valid, hot_tenants,
        hot_value_ids, wk_in, wscale_in, row(warm_valid),
        row(warm_tenants), row(warm_value_ids), row(warm_write_seq),
        centroids, members, meta]
    out_shape = (jax.ShapeDtypeStruct((Q, k), jnp.float32),
                 jax.ShapeDtypeStruct((Q, k), jnp.int32),
                 jax.ShapeDtypeStruct((Q, k), jnp.int32),
                 jax.ShapeDtypeStruct((Q, 1), jnp.int32),
                 jax.ShapeDtypeStruct((Q, 2), jnp.int32))
    fn = pl.pallas_call(
        functools.partial(_kernel, ensemble=ensemble, k=k, block_n=bn,
                          n_hot=n_hot, n_hot_blocks=n_blocks,
                          warm_block_n=wb, n_warm=cap, n_probe=n_probe,
                          tail=tail, quantized=quantized),
        grid=grid,
        in_specs=in_specs,
        out_specs=(whole((Q, k)), whole((Q, k)), whole((Q, k)),
                   whole((Q, 1)), whole((Q, 2))),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Q, k), dt) for dt in (
            jnp.float32, jnp.int32, jnp.int32,           # hot s / slot / vid
            jnp.float32, jnp.int32, jnp.int32, jnp.int32)],  # warm s/pos/slot/vid
        interpret=interpret,
    )
    out_s, out_v, out_w, hslot, flags = fn(*args)
    return (out_s, out_v, out_w, hslot[:, 0], flags[:, 1] != 0,
            flags[:, 0] != 0)


_STATIC = ("k", "n_probe", "tail", "block_n", "warm_block_n", "interpret",
           "quantized")


@functools.partial(jax.jit, static_argnames=_STATIC)
def cascade_lookup_ensemble(q, weights, q_tenants, thresholds,
                            hot_keys, hot_valid, hot_tenants, hot_value_ids,
                            warm_keys, warm_valid, warm_tenants,
                            warm_value_ids, warm_write_seq, centroids,
                            members, cursor, indexed_total,
                            warm_keys_q=None, warm_scales=None,
                            k: int = 1, n_probe: int = 8, tail: int = 0, *,
                            quantized: bool = False,
                            block_n: int = DEFAULT_BLOCK_N,
                            warm_block_n: int | None = None,
                            interpret: bool):
    """Fused E-panel ensemble cascade; signature/semantics of
    `ref.ensemble_lookup`.

    q: (E, Q, D) unit-norm stacked queries; weights: (Q, E) per-query
    mixture weights; hot_keys: (E, Nh, D); warm panels (E, cap, D)
    (int8 + (E, cap) scales when ``quantized``).  Per-slot metadata and
    the pilot-built IVF are shared across panels.  One grid sweep
    streams all E panels block-aligned — each grid step fetches one
    (E, block, D) stacked tile, so HBM traffic grows with E only for
    the key panels themselves while routing, masks, index arithmetic
    and the running top-k stay single-copy.  Returns the 6-tuple of
    `cascade_lookup` with fused scores.
    """
    return _fused_call(
        q, weights, q_tenants, thresholds, hot_keys, hot_valid, hot_tenants,
        hot_value_ids, warm_keys, warm_valid, warm_tenants, warm_value_ids,
        warm_write_seq, centroids, members, cursor, indexed_total,
        warm_keys_q, warm_scales, k, n_probe, tail, quantized, block_n,
        warm_block_n, interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cascade_lookup(q, q_tenants, thresholds,
                   hot_keys, hot_valid, hot_tenants, hot_value_ids,
                   warm_keys, warm_valid, warm_tenants, warm_value_ids,
                   warm_write_seq, centroids, members, cursor, indexed_total,
                   warm_keys_q=None, warm_scales=None,
                   k: int = 1, n_probe: int = 8, tail: int = 0, *,
                   quantized: bool = False,
                   block_n: int = DEFAULT_BLOCK_N,
                   warm_block_n: int | None = None, interpret: bool):
    """Array-level fused cascade; signature/semantics of `ref.py`.

    q: (Q, D) unit-norm.  Returns (scores (Q, k), value_ids (Q, k),
    warm_slots (Q, k), hot_slots (Q,), hot_hit (Q,), hit (Q,)).
    ``quantized=True`` streams ``warm_keys_q``/``warm_scales`` instead
    of the fp32 warm panel.  ``warm_block_n`` streams the warm key
    panel in blocks of that many rows (None = one block, the old
    whole-panel residency); results are bit-identical for every block
    count.
    """
    return _fused_call(
        q, None, q_tenants, thresholds, hot_keys, hot_valid, hot_tenants,
        hot_value_ids, warm_keys, warm_valid, warm_tenants, warm_value_ids,
        warm_write_seq, centroids, members, cursor, indexed_total,
        warm_keys_q, warm_scales, k, n_probe, tail, quantized, block_n,
        warm_block_n, interpret)
