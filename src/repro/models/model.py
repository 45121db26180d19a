"""Top-level models.

Decoder LM (all 10 assigned backbones) and encoder embedder (the paper's
ModernBERT / LangCache-Embed arch) share one parameter layout:

    params = {
      "embed":   {table, [unembed]},
      "layers":  {"pos0": <stacked over n_periods>, "pos1": ..., ...},
      "final_norm": {...},
    }

Layers are stacked along a leading ``layers`` axis and executed with
``jax.lax.scan`` over periods — O(1) HLO size for 88-layer models, which
keeps the 512-device dry-run compiles tractable (DESIGN.md §3).  The
period body is optionally rematerialised (cfg.remat) for training.

Modality frontends (audio codec / ViT) are stubs per the assignment:
``frontend_embeds`` of shape (B, frontend_len, d_model) are prepended to
the token embeddings.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, layers
from repro.models.actsharding import constrain_batch
from repro.models.param import (
    A, Initializer, Param, prefix_axes, split, stack_params, stack_values,
)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_lm(cfg: ModelConfig, key: Optional[jax.Array] = None,
            abstract: bool = False):
    """Returns a Param tree (values may be ShapeDtypeStructs if abstract)."""
    if not abstract and key is None:
        key = jax.random.PRNGKey(0)
    ini = Initializer(key, dtype=jnp.dtype(cfg.param_dtype), abstract=abstract)
    params = {"embed": layers.init_embedding(ini, cfg)}
    layer_params = {}
    for i, spec in enumerate(cfg.period):
        copies = [blocks.init_layer(ini, cfg, spec) for _ in range(cfg.n_periods)]
        layer_params[f"pos{i}"] = stack_params(copies)
    params["layers"] = layer_params
    params["final_norm"] = layers.init_norm(ini, cfg)
    return params


def lm_param_specs(cfg: ModelConfig):
    """(abstract_values, encoded_axes) for the dry-run path."""
    tree = init_lm(cfg, abstract=True)
    return split(tree)


# ---------------------------------------------------------------------------
# Forward (training / encoder full-sequence)
# ---------------------------------------------------------------------------

def _input_embeds(pv, cfg: ModelConfig, tokens, frontend_embeds):
    x = layers.embed_tokens(pv["embed"], cfg, tokens)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    if not cfg.use_rope and cfg.family == "audio":
        x = x + layers.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    # anchor batch sharding so the FSDP table sharding cannot flip the
    # whole network to batch-replicated (§Perf H6)
    return constrain_batch(x)


def _slice_period(layer_params, j):
    return jax.tree_util.tree_map(lambda a: a[j], layer_params)


def _run_layers(pv, cfg: ModelConfig, x, positions):
    """Apply all layers (scan over periods, or unrolled for dry-runs).
    Returns (x, aux)."""

    def body(carry, layer_p):
        x, aux = carry
        for i, spec in enumerate(cfg.period):
            x, a = blocks.apply_full(layer_p[f"pos{i}"], cfg, spec, x,
                                     positions)
            x = constrain_batch(x)
            aux = aux + a
        return (x, aux), None

    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.scan_layers:
        if cfg.remat:
            body = jax.checkpoint(body)
        carry, _ = jax.lax.scan(body, carry, pv["layers"])
    else:
        for j in range(cfg.n_periods):
            carry, _ = body(carry, _slice_period(pv["layers"], j))
    return carry


def forward_lm(pv, cfg: ModelConfig, tokens, frontend_embeds=None):
    """pv: plain-value param tree.  Returns (logits, aux_loss).

    tokens: (B, S_tok) int32; frontend_embeds: (B, S_fe, d) or None.
    Logits cover the *full* (frontend + token) sequence.
    """
    x = _input_embeds(pv, cfg, tokens, frontend_embeds)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, aux = _run_layers(pv, cfg, x, positions)
    x = layers.apply_norm(pv["final_norm"], cfg, x)
    logits = layers.unembed(pv["embed"], cfg, x)
    return logits, aux


def encode(pv, cfg: ModelConfig, tokens, mask=None):
    """Sentence embeddings for the encoder config (mean-pool + L2 norm).

    tokens: (B, S); mask: (B, S) bool validity (None -> all valid).
    Returns (B, d_model) float32, unit-norm — the cache key vectors.
    """
    assert cfg.is_encoder, f"{cfg.name} is not an encoder config"
    x = layers.embed_tokens(pv["embed"], cfg, tokens)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _ = _run_layers(pv, cfg, x, positions)
    x = layers.apply_norm(pv["final_norm"], cfg, x).astype(jnp.float32)
    if mask is None:
        emb = jnp.mean(x, axis=1)
    else:
        m = mask.astype(jnp.float32)[..., None]
        emb = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def init_lm_state(cfg: ModelConfig, batch: int, seq_len: int,
                  abstract: bool = False):
    """Decode-state pytree: per period-position, stacked over periods,
    plus the scalar ``cur_len`` (tokens already consumed)."""
    layer_states = {}
    for i, spec in enumerate(cfg.period):
        copies = [blocks.init_layer_state(cfg, spec, batch, seq_len, abstract)
                  for _ in range(cfg.n_periods)]
        layer_states[f"pos{i}"] = stack_values(copies)
    cur = (jax.ShapeDtypeStruct((), jnp.int32) if abstract
           else jnp.zeros((), jnp.int32))
    return {"layers": layer_states, "cur_len": cur}


def lm_state_axes(cfg: ModelConfig):
    layer_axes = {}
    for i, spec in enumerate(cfg.period):
        layer_axes[f"pos{i}"] = prefix_axes(blocks.layer_state_axes(cfg, spec))
    return {"layers": layer_axes, "cur_len": A()}


# ---------------------------------------------------------------------------
# Prefill + decode
# ---------------------------------------------------------------------------

def prefill(pv, cfg: ModelConfig, tokens, cache_len: int,
            frontend_embeds=None, mask=None):
    """Full forward over the prompt, building the decode state.

    ``mask`` ((B, S_tok) bool, None = all valid) marks the real tokens
    of left-padded prompts: pads reach neither the attention cache nor
    the Mamba state, so each row's logits are those it gets alone and
    unpadded (positions are not shifted: rotary positions are relative,
    and Jamba has none).  Returns (last_token_logits, state).
    """
    x = _input_embeds(pv, cfg, tokens, frontend_embeds)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    if mask is not None and frontend_embeds is not None:
        mask = jnp.concatenate(
            [jnp.ones((B, S - mask.shape[1]), bool), mask], axis=1)

    def body(x, layer_p):
        states = {}
        for i, spec in enumerate(cfg.period):
            x, ns, _ = blocks.apply_prefill(
                layer_p[f"pos{i}"], cfg, spec, x, positions,
                blocks.init_layer_state(cfg, spec, B, cache_len), mask)
            states[f"pos{i}"] = ns
        return x, states

    if cfg.scan_layers:
        x, layer_states = jax.lax.scan(body, x, pv["layers"])
    else:
        per_period = []
        for j in range(cfg.n_periods):
            x, st = body(x, _slice_period(pv["layers"], j))
            per_period.append(st)
        layer_states = stack_values(per_period)
    x = layers.apply_norm(pv["final_norm"], cfg, x)
    logits = layers.unembed(pv["embed"], cfg, x[:, -1:])[:, 0]
    state = {"layers": layer_states,
             "cur_len": jnp.asarray(S, jnp.int32)}
    return logits, state


def decode_step(pv, cfg: ModelConfig, state, tokens):
    """One decode step.  tokens: (B, 1) int32.  Returns (logits, state)."""
    x = layers.embed_tokens(pv["embed"], cfg, tokens)
    cur_len = state["cur_len"]
    if not cfg.use_rope and cfg.family == "audio":
        # one sinusoidal row at the current position
        pos_emb = layers.sinusoidal_positions(1, cfg.d_model, offset=cur_len)
        x = x + pos_emb.astype(x.dtype)[None]

    def body(x, xs):
        layer_p, layer_s = xs
        new_states = {}
        for i, spec in enumerate(cfg.period):
            x, ns, _ = blocks.apply_decode(
                layer_p[f"pos{i}"], cfg, spec, x, cur_len, layer_s[f"pos{i}"])
            new_states[f"pos{i}"] = ns
        return x, new_states

    if cfg.scan_layers:
        x, new_layer_states = jax.lax.scan(
            body, x, (pv["layers"], state["layers"]))
    else:
        per_period = []
        for j in range(cfg.n_periods):
            x, st = body(x, (_slice_period(pv["layers"], j),
                             _slice_period(state["layers"], j)))
            per_period.append(st)
        new_layer_states = stack_values(per_period)
    x = layers.apply_norm(pv["final_norm"], cfg, x)
    logits = layers.unembed(pv["embed"], cfg, x)[:, 0]
    return logits, {"layers": new_layer_states, "cur_len": cur_len + 1}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _nll(pv, cfg, x_pred, tgt):
    """x_pred: (B, T, d) hidden states; tgt: (B, T) — mean NLL."""
    logits = layers.unembed(pv["embed"], cfg, x_pred).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def lm_loss(pv, cfg: ModelConfig, tokens, frontend_embeds=None):
    """Next-token cross entropy (+ MoE aux).  tokens: (B, S).

    With cfg.loss_chunk > 0 the unembed is fused into the loss over
    sequence chunks, so the (B, S, vocab) logits tensor never fully
    materialises (§Perf lever; exact same value).
    """
    x = _input_embeds(pv, cfg, tokens, frontend_embeds)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, aux = _run_layers(pv, cfg, x, positions)
    x = layers.apply_norm(pv["final_norm"], cfg, x)
    # predictions for token t+1 come from stream position (n_fe + t)
    n_fe = 0 if frontend_embeds is None else frontend_embeds.shape[1]
    x_pred = x[:, n_fe:-1]                      # (B, T, d)
    tgt = tokens[:, 1:]                         # (B, T)
    T = tgt.shape[1]
    if cfg.loss_chunk and cfg.loss_chunk < T:
        C = cfg.loss_chunk
        total = jnp.zeros((), jnp.float32)
        for lo in range(0, T, C):               # unrolled (dry-run mode)
            total = total + _nll(pv, cfg, x_pred[:, lo:lo + C],
                                 tgt[:, lo:lo + C])
        nll = total / (tgt.shape[0] * T)
    else:
        nll = _nll(pv, cfg, x_pred, tgt) / (tgt.shape[0] * T)
    return nll + aux, {"nll": nll, "aux": aux}
