"""GQA/MQA attention: training (full-seq), prefill (+KV cache build) and
single-token decode against a (possibly ring-buffer) KV cache.

TPU adaptation notes (DESIGN.md §3):
 * full-sequence attention uses an online-softmax *chunked* formulation
   (lax.scan over KV blocks) above ``CHUNK_THRESHOLD`` — flash-attention
   expressed in XLA, O(S·chunk) memory instead of O(S²).  The Pallas
   kernel in repro/kernels/flash_attention is the hand-tiled variant of
   the same math; `ops.flash_attention` picks kernel vs this fallback.
 * RoPE is applied to K at cache-write time, so decode needs no position
   recompute; the ring buffer (sliding window) stores absolute positions
   per slot for masking.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.param import Initializer

CHUNK_THRESHOLD = 2048
KV_CHUNK = 1024
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(ini: Initializer, cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": ini.lecun((d, h, hd), ("embed", "heads", "head_dim"), fan_in=d),
        "wk": ini.lecun((d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": ini.lecun((d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": ini.lecun((h, hd, d), ("heads", "head_dim", "embed"), fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = ini.zeros((h, hd), ("heads", "head_dim"))
        p["bk"] = ini.zeros((kv, hd), ("kv_heads", "head_dim"))
        p["bv"] = ini.zeros((kv, hd), ("kv_heads", "head_dim"))
    return p


def _project_qkv(p, cfg: ModelConfig, x):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _out_proj(p, cfg: ModelConfig, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# Core attention math (GQA, masked, dense or chunked)
# ---------------------------------------------------------------------------

def _mask_logits(scores, q_pos, kv_pos, *, causal, window, kv_valid):
    """scores: (..., S_q, S_kv); q_pos: (S_q,); kv_pos: (S_kv,) or (B,S_kv)."""
    if kv_pos.ndim == 1:
        kv_pos = kv_pos[None]            # (1, S_kv)
    rel_q = q_pos[None, :, None]          # (1, S_q, 1)
    rel_k = kv_pos[:, None, :]            # (B|1, 1, S_kv)
    ok = jnp.ones(jnp.broadcast_shapes(rel_q.shape, rel_k.shape), bool)
    if causal:
        ok &= rel_k <= rel_q
    if window > 0:
        ok &= (rel_q - rel_k) < window
        if not causal:
            ok &= (rel_k - rel_q) < window
    if kv_valid is not None:
        ok &= kv_valid[:, None, :]
    # broadcast over head dims: scores (B, KV, G, S_q, S_kv)
    return jnp.where(ok[:, None, None], scores, NEG_INF)


def gqa_attention(q, k, v, *, q_pos, kv_pos, causal, window,
                  kv_valid=None, chunked: Optional[bool] = None,
                  unroll: bool = False, acc_dtype=jnp.float32):
    """q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd).  Returns (B,Sq,H,hd).

    unroll=True replaces the KV-chunk lax.scan with a python loop so the
    dry-run's cost_analysis counts every chunk (see ModelConfig
    .scan_layers); it also widens chunks to bound HLO size.

    acc_dtype: dtype of the softmax probabilities and the PV
    accumulator (the two big attention buffers).  Logit max/denominator
    stay f32.  bf16 here halves attention HBM traffic — the ModelConfig
    .attn_f32=False §Perf lever.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, G, hd) * scale
    if chunked is None:
        chunked = Skv > CHUNK_THRESHOLD and Sq > 1
    if not chunked:
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                       k.astype(jnp.float32))
        s = _mask_logits(s, q_pos, kv_pos, causal=causal, window=window,
                         kv_valid=kv_valid)
        w = jax.nn.softmax(s, axis=-1).astype(acc_dtype)
        o = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(acc_dtype))
        return o.reshape(B, Sq, H, hd).astype(q.dtype)

    # ---- chunked online-softmax (flash-in-XLA) ----
    kv_chunk = KV_CHUNK if not unroll else max(KV_CHUNK, -(-Skv // 32))
    n_chunks = -(-Skv // kv_chunk)
    pad = n_chunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos_p = jnp.pad(kv_pos, [(0, 0)] * (kv_pos.ndim - 1) + [(0, pad)],
                           constant_values=-1)
        valid_pad = jnp.pad(
            kv_valid if kv_valid is not None
            else jnp.ones((B, Skv), bool),
            ((0, 0), (0, pad)), constant_values=False)
    else:
        kv_pos_p = kv_pos
        valid_pad = kv_valid if kv_valid is not None else jnp.ones((B, Skv), bool)

    kc = k.reshape(B, n_chunks, kv_chunk, KV, hd)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, hd)
    if kv_pos_p.ndim == 1:
        kv_pos_p = jnp.broadcast_to(kv_pos_p[None], (B, n_chunks * kv_chunk))
    pc = kv_pos_p.reshape(B, n_chunks, kv_chunk)
    mc = valid_pad.reshape(B, n_chunks, kv_chunk)

    def body(carry, xs):
        m, l, acc = carry
        k_i, v_i, p_i, valid_i = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                       k_i.astype(jnp.float32))
        s = _mask_logits(s, q_pos, p_i, causal=causal, window=window,
                         kv_valid=valid_i)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None]).astype(acc_dtype)
        l_new = l * alpha + jnp.sum(p_, axis=-1, dtype=jnp.float32)
        acc_new = acc * alpha[..., None].astype(acc_dtype) + jnp.einsum(
            "bkgqs,bskh->bkgqh", p_, v_i.astype(acc_dtype))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), acc_dtype)
    if unroll:
        carry = (m0, l0, a0)
        for i in range(n_chunks):
            carry, _ = body(carry, (kc[:, i], vc[:, i], pc[:, i], mc[:, i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
             pc.transpose(1, 0, 2), mc.transpose(1, 0, 2)))
    o = acc.astype(jnp.float32) / jnp.maximum(l[..., None], 1e-30)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return o.astype(q.dtype)


def local_window_attention(q, k, v, *, positions, window, causal,
                           acc_dtype=jnp.float32, q_chunk: int = 1024):
    """Structurally-sparse sliding-window attention: each q chunk
    attends only to its (window + chunk) KV slice — O(S·W) traffic
    instead of O(S²)-with-masking.  This is what the Pallas kernel's
    @pl.when block-skipping achieves; the XLA fallback needs the
    blocking to be explicit (static slices, unrolled — §Perf lever).
    """
    B, S, H, hd = q.shape
    C = min(q_chunk, S)
    nq = -(-S // C)
    pad = nq * C - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, (0, pad),
                            constant_values=int(positions.shape[0]) - 1)
    outs = []
    for iq in range(nq):
        q_lo = iq * C
        q_hi = min(q_lo + C, S)
        kv_lo = max(0, q_lo - window + 1)
        o = gqa_attention(
            q[:, q_lo:q_lo + C], k[:, kv_lo:q_hi], v[:, kv_lo:q_hi],
            q_pos=positions[q_lo:q_lo + C], kv_pos=positions[kv_lo:q_hi],
            causal=causal, window=window, chunked=False,
            acc_dtype=acc_dtype)
        outs.append(o)
    out = jnp.concatenate(outs, axis=1)
    return out[:, :S]


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------

def apply_full(p, cfg: ModelConfig, x, positions):
    """Full-sequence attention (training / encoder).  x: (B,S,d)."""
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.use_rope:
        sin, cos = layers.rope_frequencies(cfg, positions)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    acc_dtype = jnp.float32 if cfg.attn_f32 else jnp.bfloat16
    W = cfg.sliding_window
    if W > 0 and cfg.causal and x.shape[1] > 2 * W:
        o = local_window_attention(q, k, v, positions=positions, window=W,
                                   causal=True, acc_dtype=acc_dtype,
                                   q_chunk=min(1024, W))
    else:
        o = gqa_attention(q, k, v, q_pos=positions, kv_pos=positions,
                          causal=cfg.causal, window=W,
                          unroll=cfg.unroll_inner, acc_dtype=acc_dtype)
    return _out_proj(p, cfg, o)


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    return min(cfg.sliding_window, seq_len) if cfg.sliding_window > 0 else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, abstract: bool = False):
    """Empty KV cache for one attention layer."""
    L = cache_len_for(cfg, seq_len)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    shapes = {
        "k": ((batch, L, kv, hd), dt),
        "v": ((batch, L, kv, hd), dt),
        "pos": ((batch, L), jnp.dtype(jnp.int32)),
    }
    if abstract:
        return {n: jax.ShapeDtypeStruct(s, d) for n, (s, d) in shapes.items()}
    out = {n: jnp.zeros(s, d) for n, (s, d) in shapes.items() if n != "pos"}
    out["pos"] = jnp.full(shapes["pos"][0], -1, jnp.int32)
    return out


def cache_axes():
    return {
        "k": ("batch", "cache", "kv_heads", "head_dim"),
        "v": ("batch", "cache", "kv_heads", "head_dim"),
        "pos": ("batch", "cache"),
    }


def apply_prefill(p, cfg: ModelConfig, x, positions, cache, mask=None):
    """Run full attention over the prompt AND fill the cache.

    Returns (y, new_cache).  With a sliding window the cache keeps only
    the last `window` tokens, written at slots (t mod window).  ``mask``
    ((B,S) bool, None = all valid) marks each row's real tokens: pad
    keys are masked here and get ``pos`` -1 in the cache, so decode
    masks them too.
    """
    B, S, _ = x.shape
    L = cache["k"].shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.use_rope:
        sin, cos = layers.rope_frequencies(cfg, positions)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    o = gqa_attention(q, k, v, q_pos=positions, kv_pos=positions,
                      causal=True, window=cfg.sliding_window,
                      kv_valid=mask, unroll=cfg.unroll_inner,
                      acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16)
    y = _out_proj(p, cfg, o)

    pos = jnp.broadcast_to(positions.astype(jnp.int32)[None], (B, S))
    if mask is not None:
        pos = jnp.where(mask, pos, -1)
    if L >= S:
        new_k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                             (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                             (0, 0, 0, 0))
        new_pos = jnp.pad(pos, ((0, 0), (0, L - S)), constant_values=-1)
    else:
        # keep last L tokens, slot t % L
        slots = jnp.mod(positions[S - L:], L)              # (L,)
        new_k = cache["k"].at[:, slots].set(k[:, S - L:].astype(cache["k"].dtype))
        new_v = cache["v"].at[:, slots].set(v[:, S - L:].astype(cache["v"].dtype))
        new_pos = jnp.zeros((B, L), jnp.int32).at[:, slots].set(pos[:, S - L:])
    return y, {"k": new_k, "v": new_v, "pos": new_pos}


def apply_decode(p, cfg: ModelConfig, x, cur_len, cache):
    """One-token decode.  x: (B,1,d); cur_len: () int32 — tokens already
    in the cache (the new token's absolute position)."""
    B = x.shape[0]
    L = cache["k"].shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.use_rope:
        pos = jnp.asarray(cur_len, jnp.int32)[None]      # (1,)
        sin, cos = layers.rope_frequencies(cfg, pos)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    slot = jnp.mod(cur_len, L)
    new_k = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    new_pos = jax.lax.dynamic_update_slice(
        cache["pos"],
        jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32)[None, None], (B, 1)),
        (0, slot))
    valid = new_pos >= 0
    q_pos = jnp.asarray(cur_len, jnp.int32)[None]
    o = gqa_attention(q, new_k, new_v, q_pos=q_pos, kv_pos=new_pos,
                      causal=True, window=cfg.sliding_window,
                      kv_valid=valid, chunked=False)
    y = _out_proj(p, cfg, o)
    return y, {"k": new_k, "v": new_v, "pos": new_pos}
