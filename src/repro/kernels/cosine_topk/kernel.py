"""Pallas TPU kernel: fused cosine-similarity top-k over a blocked corpus.

The semantic cache's serving hot path (DESIGN.md §3).  The corpus is
streamed through VMEM in (BLOCK_N × D) tiles; the query tile stays
resident; the MXU computes the (Q × BLOCK_N) score panel; and a running
top-k (scores+indices) is carried in VMEM scratch across grid steps —
the (Q × N) score matrix never exists in HBM.

Top-k selection (`select_topk`, shared with `kernels/cascade_lookup`)
runs k rounds of max + first-column-at-max over the candidate segments
(accumulator first, then the block), which vectorises on the VPU — no
sort network and no gather: the winner is found by an iota compare and
its payload read back by a masked sum, the only forms Mosaic lowers.
Masks travel as int32 (1, N) rows so their blocks tile like the keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_N = 512
# tie-key sentinel for masked / consumed candidates (larger than any
# real tie key the callers use)
POS_PAD = 2 ** 30


def select_topk(k, segments, tie: bool = False):
    """Top-k over the concatenation of ``segments`` without building it.

    Each segment is ``(scores (Q, M), *payloads)`` with int32 payloads of
    the same shape; candidates are ordered segment by segment, column by
    column.  Each of the k rounds takes the best score, then (``tie``)
    the lowest first payload among the entries holding it, then the
    earliest candidate — `jax.lax.top_k`'s lowest-index-first order,
    with the tie key taking precedence over position when given.
    Returns ``(scores (Q, k), [payload (Q, k), ...])``.
    """
    segs = [list(s) for s in segments]
    Q = segs[0][0].shape[0]
    n_pay = len(segs[0]) - 1
    kcol = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1)
    out_s = jnp.full((Q, k), NEG_INF, jnp.float32)
    out_p = [jnp.zeros((Q, k), jnp.int32) for _ in range(n_pay)]
    cols = [jax.lax.broadcasted_iota(jnp.int32, s[0].shape, 1) for s in segs]
    for r in range(k):
        m = functools.reduce(jnp.maximum, [
            jnp.max(s[0], axis=-1, keepdims=True) for s in segs])
        at = [s[0] >= m for s in segs]
        if tie:
            t = functools.reduce(jnp.minimum, [
                jnp.min(jnp.where(a, s[1], POS_PAD), axis=-1, keepdims=True)
                for a, s in zip(at, segs)])
            at = [a & (s[1] == t) for a, s in zip(at, segs)]
        taken = jnp.zeros((Q, 1), bool)
        vals = [jnp.zeros((Q, 1), jnp.int32) for _ in range(n_pay)]
        for a, s, col in zip(at, segs, cols):
            width = s[0].shape[1]
            first = jnp.min(jnp.where(a, col, width), axis=-1, keepdims=True)
            sel = (col == first) & ~taken
            taken = taken | (first < width)
            for i in range(n_pay):
                vals[i] = vals[i] + jnp.sum(jnp.where(sel, s[1 + i], 0),
                                            axis=-1, keepdims=True)
            s[0] = jnp.where(sel, NEG_INF, s[0])
            if tie:
                s[1] = jnp.where(sel, POS_PAD, s[1])
        here = kcol == r
        out_s = jnp.where(here, m, out_s)
        out_p = [jnp.where(here, v, o) for v, o in zip(vals, out_p)]
    return out_s, out_p


def _kernel(q_ref, keys_ref, valid_ref, out_s_ref, out_i_ref,
            acc_s, acc_i, *, k: int, block_n: int, n_total: int):
    j = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.full_like(acc_s, NEG_INF)
        acc_i[...] = jnp.zeros_like(acc_i)

    q = q_ref[...].astype(jnp.float32)                # (Q, D)
    kblk = keys_ref[...].astype(jnp.float32)          # (BN, D)
    s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, BN)
    col = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = (valid_ref[...] != 0) & (col < n_total)      # (1, BN) row
    s = jnp.where(ok, s, NEG_INF)

    new_s, (new_i,) = select_topk(k, [(acc_s[...], acc_i[...]), (s, col)])
    acc_s[...] = new_s
    acc_i[...] = new_i

    @pl.when(j == nb - 1)
    def _done():
        out_s_ref[...] = acc_s[...]
        out_i_ref[...] = acc_i[...]


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def cosine_topk(q, keys, valid, k: int = 1, *,
                block_n: int = DEFAULT_BLOCK_N, interpret: bool):
    """q: (Q, D); keys: (N, D); valid: (N,).  -> ((Q,k) scores, (Q,k) idx)."""
    Q, D = q.shape
    N = keys.shape[0]
    bn = min(block_n, N)
    n_blocks = -(-N // bn)
    pad = n_blocks * bn - N
    valid = valid.astype(jnp.int32)[None, :]          # (1, N) int32 row
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))

    grid = (n_blocks,)
    out_shape = (jax.ShapeDtypeStruct((Q, k), jnp.float32),
                 jax.ShapeDtypeStruct((Q, k), jnp.int32))
    fn = pl.pallas_call(
        functools.partial(_kernel, k=k, block_n=bn, n_total=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q, D), lambda j: (0, 0)),
            pl.BlockSpec((bn, D), lambda j: (j, 0)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=(pl.BlockSpec((Q, k), lambda j: (0, 0)),
                   pl.BlockSpec((Q, k), lambda j: (0, 0))),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((Q, k), jnp.float32),
            pltpu.VMEM((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )
    return fn(q, keys, valid)
