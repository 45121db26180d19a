"""Pure-jnp oracle for the fused online-contrastive loss kernel.

Returns the *components* (pos_loss_sum, neg_loss_sum, min_neg, max_pos)
— the op wrapper assembles the final scalar exactly like
repro.core.losses.online_contrastive_loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BIG = 1e9


def pair_distance(e1, e2):
    """Cosine distance of row pairs, f32 (the kernel's own form)."""
    num = jnp.sum(e1 * e2, axis=-1)
    den = (jnp.sqrt(jnp.sum(e1 * e1, axis=-1)) *
           jnp.sqrt(jnp.sum(e2 * e2, axis=-1)))
    return 1.0 - num / jnp.maximum(den, 1e-9)


# jitted so that, called eagerly, each product and its sum fuse into one
# loop as they do inside the kernel (as separate ops they round
# differently on the CPU)
@functools.partial(jax.jit, static_argnames=("margin",))
def contrastive_components(e1, e2, labels, margin: float = 0.5):
    d = pair_distance(e1.astype(jnp.float32), e2.astype(jnp.float32))
    is_pos = labels.astype(bool)
    is_neg = ~is_pos
    min_neg = jnp.min(jnp.where(is_neg, d, BIG))
    max_pos = jnp.max(jnp.where(is_pos, d, -BIG))
    hard_pos = is_pos & (d > min_neg)
    hard_neg = is_neg & (d < max_pos)
    pos_loss = jnp.sum(jnp.square(d) * hard_pos)
    neg_loss = jnp.sum(jnp.square(jnp.maximum(margin - d, 0.0)) * hard_neg)
    return pos_loss, neg_loss, min_neg, max_pos
