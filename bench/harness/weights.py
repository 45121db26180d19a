"""Encoder weights made from the seed, on the device, in one jitted call.

The tree has the layout the system under test reads (its `init_lm` tree
after `split`): one stacked leaf per layer kind, f32 master weights.
The same arrays feed the plain reference in `bench/reference/encoder.py`,
so the reference takes nothing that the program has made.

Norm scales and biases are drawn around 1 and 0 rather than set to
them, so a path that drops a norm's scale or bias shows in the check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits (the system's seeds
    do not fit 32 signed bits)."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def encoder_shapes(m: dict) -> dict:
    """Leaf shapes of the encoder tree for the sizes in a config file."""
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h = m["num_attention_heads"]
    hd = d // h
    norm = {"scale": (L, d), "bias": (L, d)}
    return {
        "embed": {"table": (m["vocab_size"], d)},
        "layers": {"pos0": {
            "norm1": dict(norm),
            "mixer": {"wq": (L, d, h, hd), "wk": (L, d, h, hd),
                      "wv": (L, d, h, hd), "wo": (L, h, hd, d)},
            "norm2": dict(norm),
            "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        }},
        "final_norm": {"scale": (d,), "bias": (d,)},
    }


def _std(name: str, shape) -> tuple:
    """(mean, stddev) of one leaf, by its name."""
    if name == "scale":
        return 1.0, 0.1
    if name == "bias":
        return 0.0, 0.1
    if name == "table":
        return 0.0, 0.02
    if name == "wo":
        return 0.0, (shape[1] * shape[2]) ** -0.5
    return 0.0, shape[1] ** -0.5            # fan-in of the (L, in, ...) leaf


def make_encoder_params(m: dict, seed: int) -> dict:
    """The whole f32 tree on the default device, from one jitted call."""
    shapes = encoder_shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    specs = [(p[-1].key, s) for p, s in leaves]

    def build(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for k, (name, shape) in zip(keys, specs):
            mu, sd = _std(name, shape)
            out.append(mu + sd * jax.random.normal(k, shape, jnp.float32))
        return out

    vals = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, vals)
