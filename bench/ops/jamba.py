"""Operations and bytes of the Jamba LLM behind the cache, from its sizes
(`harness.jamba_weights.jamba_sizes`), with bfloat16 weights, a float32
SSM state and a bfloat16 conv state and KV cache, as the program holds
them.

Operations count the matrix products (two per multiply-add: the layers'
projections, the conv taps, the MLP and the tied output head), the
attention scores and weighted sums over the context, and the selective
scan's update of h (six per state element and token: dt·A, dt·B·x, the
decay, the add, C·h and its sum).  A decode step reads every weight
once (the tied table serves as the output head), reads and writes every
Mamba layer's h and conv window, and reads the attention layers' cache.
"""
from __future__ import annotations

SCAN_OPS = 6


def _layer_kinds(m: dict):
    n_attn = sum(i % m["period"] == m["offset"] for i in range(m["layers"]))
    return n_attn, m["layers"] - n_attn


def _mixer_weights(m: dict) -> dict:
    d, di, N, K, R = (m["d"], m["d_inner"], m["d_state"], m["d_conv"],
                      m["dt_rank"])
    h, kv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    return {
        # in_proj, conv (+bias), x_proj, dt_proj (+bias), A_log, D,
        # out_proj, the dt/B/C norms
        "mamba": (d * 2 * di + (K + 1) * di + di * (R + 2 * N)
                  + (R + 1) * di + di * N + di + di * d + R + 2 * N),
        "attn": d * h * hd + 2 * d * kv * hd + h * hd * d,
        "mlp": 3 * d * m["ff"],
    }


def param_count(m: dict) -> int:
    n_attn, n_mamba = _layer_kinds(m)
    w = _mixer_weights(m)
    return (m["vocab"] * m["d"] + n_attn * w["attn"] + n_mamba * w["mamba"]
            + m["layers"] * (w["mlp"] + 2 * m["d"]) + m["d"])


def _token_matmul_flops(m: dict) -> int:
    """Two per multiply-add of one token through every layer's
    projections and MLP (no output head)."""
    n_attn, n_mamba = _layer_kinds(m)
    d, di, N, K, R = (m["d"], m["d_inner"], m["d_state"], m["d_conv"],
                      m["dt_rank"])
    mamba = d * 2 * di + K * di + di * (R + 2 * N) + R * di + di * d
    return 2 * (n_mamba * mamba + n_attn * _mixer_weights(m)["attn"]
                + m["layers"] * _mixer_weights(m)["mlp"])


def _token_mixing_flops(m: dict, context: int) -> int:
    """One token's attention over ``context`` keys and its scan update."""
    n_attn, n_mamba = _layer_kinds(m)
    return (n_attn * 4 * context * m["heads"] * m["head_dim"]
            + n_mamba * SCAN_OPS * m["d_inner"] * m["d_state"])


def step_cost(rows: int, m: dict, context: int) -> dict:
    """-> {"flops", "bytes"} of one decode step of ``rows`` rows against
    an attention cache of ``context`` slots."""
    n_attn, n_mamba = _layer_kinds(m)
    di, kv, hd = m["d_inner"], m["kv_heads"], m["head_dim"]
    flops = rows * (_token_matmul_flops(m) + _token_mixing_flops(m, context)
                    + 2 * m["d"] * m["vocab"])
    state = n_mamba * (di * m["d_state"] * 4 + (m["d_conv"] - 1) * di * 2)
    nbytes = (2 * param_count(m)
              + rows * 2 * state                     # read and written
              + rows * n_attn * (context + 1) * 2 * kv * hd * 2)
    return {"flops": float(flops), "bytes": float(nbytes)}


def generation_flops(prompt_tokens, new_tokens: int, m: dict) -> float:
    """Model operations of one generation per prompt of ``prompt_tokens``
    real tokens (an int or an iterable of ints): the prompt's forward
    with the head at its last token, then ``new_tokens - 1`` decoded
    tokens each with the head (the last token is read from the logits
    before it)."""
    ns = [prompt_tokens] if isinstance(prompt_tokens, int) \
        else list(prompt_tokens)
    n_attn, n_mamba = _layer_kinds(m)
    head = 2 * m["d"] * m["vocab"]
    att = n_attn * 4 * m["heads"] * m["head_dim"]   # per key in context
    per = _token_matmul_flops(m) + n_mamba * SCAN_OPS * m["d_inner"] \
        * m["d_state"]
    total = 0
    for n in ns:
        t = n + new_tokens - 1                     # tokens run forward
        total += t * per + att * t * (t + 1) // 2 + new_tokens * head
    return float(total)
