"""Pallas TPU kernel: blocked flash attention (prefill/training path).

MXU-oriented tiling (DESIGN.md §3): Q blocks stay VMEM-resident while KV
blocks stream; the running (m, l, acc) online-softmax state lives in
VMEM scratch across the innermost (KV) grid dimension.  GQA is handled
with *zero* KV duplication — the K/V BlockSpec index_map folds the query
head onto its KV head (h // group_size), so HBM traffic is that of the
true KV head count (this replaces the CUDA trick of shared-memory
broadcast within a warpgroup).

Causal + sliding-window masking is positional; fully-masked KV blocks
are skipped with @pl.when (a real schedule win for causal prefill:
~2× fewer MXU blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 256


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, block_q: int, block_kv: int,
            sq: int, skv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * block_q
    k_lo = ik * block_kv
    # block-level reachability (skip fully-masked blocks)
    live = jnp.asarray(True)
    if causal:
        live &= k_lo <= q_lo + block_q - 1
    if window > 0:
        live &= (q_lo - (k_lo + block_kv - 1)) < window
        if not causal:
            live &= (k_lo - (q_lo + block_q - 1)) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (BKV, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(q * hd ** -0.5, k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (BQ,BKV)
        row = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = col < skv
        if causal:
            ok &= col <= row
        if window > 0:
            ok &= (row - col) < window
            if not causal:
                ok &= (col - row) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                              # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV,
                    interpret: bool):
    """q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    nq = -(-Sq // bq)
    nk = -(-Skv // bkv)
    pad_q = nq * bq - Sq
    pad_k = nk * bkv - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (B, H, nq, nk)
    fn = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window,
                          block_q=bq, block_kv=bkv, sq=Sq, skv=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )
    out = fn(q, k, v)
    return out[:, :, :Sq] if pad_q else out
