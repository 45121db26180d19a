"""Pallas TPU kernel: flash-decode — one query token vs a long KV cache.

The decode-shape hot loop (decode_32k / long_500k).  The KV cache
streams through VMEM in (BLOCK_L × hd) tiles along the cache-length
grid axis while the online-softmax state (m, l, acc) rides in VMEM
scratch; the query vector is resident.  GQA again via index-map head
folding (no KV duplication).  Validity (ring-buffer slots, TTL holes,
sliding-window horizon) arrives as a precomputed (B, L) boolean mask —
one predicated VPU op per tile, no gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_L = 512


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr):
    il = pl.program_id(2)
    nl = pl.num_programs(2)

    @pl.when(il == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)[None, :]          # (1, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)                # (BL, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)
    valid = valid_ref[0]                                  # (BL,)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q * hd ** -0.5, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, BL)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(il == nl - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       )[0].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def decode_attention(q, k, v, kv_valid, *, block_l: int = DEFAULT_BLOCK_L,
                     interpret: bool):
    """q: (B, H, hd); k, v: (B, L, KV, hd); kv_valid: (B, L) bool."""
    B, H, hd = q.shape
    L, KV = k.shape[1], k.shape[2]
    G = H // KV
    bl = min(block_l, L)
    nl = -(-L // bl)
    pad = nl * bl - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))

    grid = (B, H, nl)
    fn = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, il: (b, h, 0)),
            pl.BlockSpec((1, bl, 1, hd), lambda b, h, il: (b, il, h // G, 0)),
            pl.BlockSpec((1, bl, 1, hd), lambda b, h, il: (b, il, h // G, 0)),
            pl.BlockSpec((1, bl), lambda b, h, il: (b, il)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, h, il: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(q, k, v, kv_valid)
