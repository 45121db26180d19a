"""Jamba weights made from the seed, on the device, in one jitted call.

The tree has the layout the system under test reads (its `init_lm` tree
after `split`): one stacked leaf per position of the 14-layer period,
stacked over the periods, bfloat16 like the published checkpoint.  The
same arrays feed the plain reference in `bench/reference/jamba.py`, so
the reference takes nothing that the program has made.

Leaves are drawn as HF's Jamba initialises them where that matters to
the numbers: dt's bias from a log-uniform step in [1e-3, 1e-1], A_log
around log([1..N]).  Norm scales, D and the conv bias are drawn around
1 and 0 rather than set to them, so a path that drops one shows in the
check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import seed_key


def jamba_sizes(cfg: dict) -> dict:
    """The model's sizes from the published keys of a configuration file
    (HF Jamba's names).  ``stack`` is the program's period, over which
    its leaves are stacked: the attention period, or every layer where
    the model is cut below one period (its first layers)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, period = cfg["num_hidden_layers"], cfg["attn_layer_period"]
    stack = min(layers, period)
    if layers % stack:
        raise ValueError(f"{layers} layers are not whole periods of "
                         f"{period}")
    return {"stack": stack,"layers": cfg["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": cfg["num_key_value_heads"], "head_dim": d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "d_inner": cfg["mamba_expand"] * d,
            "d_state": cfg["mamba_d_state"], "d_conv": cfg["mamba_d_conv"],
            "dt_rank": cfg["mamba_dt_rank"],
            "period": cfg["attn_layer_period"],
            "offset": cfg["attn_layer_offset"], "eps": cfg["rms_norm_eps"]}


def is_attention(m: dict, pos: int) -> bool:
    """HF Jamba's rule: layer i is attention iff i % period == offset."""
    return pos % m["period"] == m["offset"]


def jamba_shapes(m: dict) -> dict:
    """Leaf shapes of the program's tree for these sizes."""
    P = m["layers"] // m["stack"]
    d, f, di = m["d"], m["ff"], m["d_inner"]
    N, K, R = m["d_state"], m["d_conv"], m["dt_rank"]
    h, kv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    mamba = {"in_proj": (P, d, 2 * di), "conv_w": (P, K, di),
             "conv_b": (P, di), "x_proj": (P, di, R + 2 * N),
             "dt_w": (P, R, di), "dt_b": (P, di), "A_log": (P, di, N),
             "D": (P, di), "out_proj": (P, di, d), "dt_norm": (P, R),
             "B_norm": (P, N), "C_norm": (P, N)}
    attn = {"wq": (P, d, h, hd), "wk": (P, d, kv, hd), "wv": (P, d, kv, hd),
            "wo": (P, h, hd, d)}
    layers = {}
    for i in range(m["stack"]):
        layers[f"pos{i}"] = {
            "norm1": {"scale": (P, d)},
            "mixer": dict(attn if is_attention(m, i) else mamba),
            "norm2": {"scale": (P, d)},
            "ffn": {"w_gate": (P, d, f), "w_up": (P, d, f),
                    "w_down": (P, f, d)}}
    return {"embed": {"table": (m["vocab"], d)}, "layers": layers,
            "final_norm": {"scale": (d,)}}


def _draw(name: str, shape, key):
    """One leaf, float32, by its name."""
    normal = jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "dt_norm", "B_norm", "C_norm", "D"):
        return 1.0 + 0.1 * normal
    if name == "conv_b":
        return 0.1 * normal
    if name == "table":
        return 0.02 * normal
    if name == "dt_b":
        # inverse softplus of dt ~ exp(U(log 1e-3, log 1e-1))
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "A_log":
        return jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)) \
            + 0.1 * normal
    if name == "dt_w":
        return normal * (shape[1] ** -0.5 / np.sqrt(3.0))
    if name == "wo":
        return normal * (shape[1] * shape[2]) ** -0.5
    return normal * shape[1] ** -0.5        # fan-in of the (P, in, ...) leaf


def make_jamba_params(m: dict, seed: int) -> dict:
    """The whole bfloat16 tree on the default device, from one jitted
    call (each leaf drawn in float32 and rounded once)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        jamba_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    specs = [(p[-1].key, s) for p, s in leaves]

    def build(key):
        keys = jax.random.split(key, len(specs))
        return [_draw(name, shape, k).astype(jnp.bfloat16)
                for k, (name, shape) in zip(keys, specs)]

    vals = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, vals)
