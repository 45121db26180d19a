"""Unified layer block: pre-norm mixer + residual + (dense|MoE|no) FFN.

Dispatches on :class:`LayerSpec` so that dense, MoE, Mamba, xLSTM and
hybrid architectures all share one code path (and one scanned-params
layout).  Three entry points per layer, mirroring the mixers:

  apply_full    — full-sequence (training / encoder)          -> (x, aux)
  apply_prefill — full-sequence + build decode state          -> (x, state, aux)
  apply_decode  — one token against carried state             -> (x, state, aux)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN, DENSE, MAMBA, MLSTM, MOE, NONE, SLSTM, LayerSpec, ModelConfig,
)
from repro.models import attention, layers, mamba, moe, xlstm
from repro.models.param import A, Initializer, prefix_axes


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_layer(ini: Initializer, cfg: ModelConfig, spec: LayerSpec):
    p = {"norm1": layers.init_norm(ini, cfg)}
    if spec.mixer == ATTN:
        p["mixer"] = attention.init_attention(ini, cfg)
    elif spec.mixer == MAMBA:
        p["mixer"] = mamba.init_mamba(ini, cfg)
    elif spec.mixer == MLSTM:
        p["mixer"] = xlstm.init_mlstm(ini, cfg)
    elif spec.mixer == SLSTM:
        p["mixer"] = xlstm.init_slstm(ini, cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == DENSE:
        p["norm2"] = layers.init_norm(ini, cfg)
        p["ffn"] = layers.init_mlp(ini, cfg)
    elif spec.ffn == MOE:
        p["norm2"] = layers.init_norm(ini, cfg)
        p["ffn"] = moe.init_moe(ini, cfg)
    return p


def init_layer_state(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     seq_len: int, abstract: bool = False):
    if spec.mixer == ATTN:
        return attention.init_cache(cfg, batch, seq_len, abstract)
    if spec.mixer == MAMBA:
        return mamba.init_state(cfg, batch, abstract)
    if spec.mixer == MLSTM:
        return xlstm.init_mlstm_state(cfg, batch, abstract)
    if spec.mixer == SLSTM:
        return xlstm.init_slstm_state(cfg, batch, abstract)
    raise ValueError(spec.mixer)


def layer_state_axes(cfg: ModelConfig, spec: LayerSpec):
    if spec.mixer == ATTN:
        raw = attention.cache_axes()
    elif spec.mixer == MAMBA:
        raw = mamba.state_axes()
    elif spec.mixer == MLSTM:
        raw = xlstm.mlstm_state_axes()
    elif spec.mixer == SLSTM:
        raw = xlstm.slstm_state_axes()
    else:
        raise ValueError(spec.mixer)
    return {k: A(*v) for k, v in raw.items()}


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _ffn(p, cfg: ModelConfig, spec: LayerSpec, x):
    if spec.ffn == NONE:
        return x, jnp.zeros((), jnp.float32)
    h = layers.apply_norm(p["norm2"], cfg, x)
    if spec.ffn == DENSE:
        return x + layers.apply_mlp(p["ffn"], cfg, h), jnp.zeros((), jnp.float32)
    y, aux = moe.apply_moe(p["ffn"], cfg, h)
    return x + y, aux


def apply_full(p, cfg: ModelConfig, spec: LayerSpec, x, positions):
    h = layers.apply_norm(p["norm1"], cfg, x)
    if spec.mixer == ATTN:
        y = attention.apply_full(p["mixer"], cfg, h, positions)
    elif spec.mixer == MAMBA:
        y = mamba.apply_full(p["mixer"], cfg, h)
    elif spec.mixer == MLSTM:
        y = xlstm.apply_mlstm_full(p["mixer"], cfg, h)
    else:
        y = xlstm.apply_slstm_full(p["mixer"], cfg, h)
    x = x + y
    return _ffn(p, cfg, spec, x)


def apply_prefill(p, cfg: ModelConfig, spec: LayerSpec, x, positions, state,
                  mask=None):
    """``mask`` ((B,S) bool, None = all valid) marks the real tokens of
    left-padded rows; only the attention and Mamba mixers take one."""
    h = layers.apply_norm(p["norm1"], cfg, x)
    if spec.mixer == ATTN:
        y, ns = attention.apply_prefill(p["mixer"], cfg, h, positions, state,
                                        mask)
    elif spec.mixer == MAMBA:
        y, ns = mamba.apply_prefill(p["mixer"], cfg, h, mask)
    elif mask is not None:
        raise NotImplementedError(f"{spec.mixer} prefill takes no pad mask")
    elif spec.mixer == MLSTM:
        y, ns = xlstm.apply_mlstm_full(p["mixer"], cfg, h, return_state=True)
    else:
        y, ns = xlstm.apply_slstm_full(p["mixer"], cfg, h, return_state=True)
    x = x + y
    x, aux = _ffn(p, cfg, spec, x)
    return x, ns, aux


def apply_decode(p, cfg: ModelConfig, spec: LayerSpec, x, cur_len, state):
    h = layers.apply_norm(p["norm1"], cfg, x)
    if spec.mixer == ATTN:
        y, ns = attention.apply_decode(p["mixer"], cfg, h, cur_len, state)
    elif spec.mixer == MAMBA:
        y, ns = mamba.apply_decode(p["mixer"], cfg, h, state)
    elif spec.mixer == MLSTM:
        y, ns = xlstm.apply_mlstm_decode(p["mixer"], cfg, h, state)
    else:
        y, ns = xlstm.apply_slstm_decode(p["mixer"], cfg, h, state)
    x = x + y
    x, aux = _ffn(p, cfg, spec, x)
    return x, ns, aux
