"""Pallas TPU kernel: fused online-contrastive loss.

sentence-transformers mines hard pairs with boolean indexing — dynamic
shapes, two passes over HBM, and a host-device sync on GPU.  The TPU
formulation (DESIGN.md §3) is a two-phase grid over batch tiles with the
cross-batch statistics carried in SMEM scratch:

  phase 0: per-tile pair distances (one fused VMEM pass: dot + norms),
           running (min_neg, max_pos) reduction into SMEM;
  phase 1: distances recomputed in VMEM (cheaper than an HBM round-trip
           for D ≤ a few K), hard-pair masks formed against the SMEM
           stats, masked loss sums accumulated.

Grid iteration on TPU is sequential-lexicographic, which is what makes
the phase-major (2, n_tiles) grid a correct two-pass schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contrastive.ref import BIG, pair_distance


def _kernel(e1_ref, e2_ref, lab_ref, out_ref, stats, *, margin: float,
            block_b: int, n_total: int):
    phase = pl.program_id(0)
    jb = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when((phase == 0) & (jb == 0))
    def _init():
        stats[0] = BIG      # min_neg
        stats[1] = -BIG     # max_pos
        stats[2] = 0.0      # pos_loss_sum
        stats[3] = 0.0      # neg_loss_sum

    e1 = e1_ref[...].astype(jnp.float32)
    e2 = e2_ref[...].astype(jnp.float32)
    lab = lab_ref[...]
    d = pair_distance(e1, e2)                              # (BB,)
    row = jb * block_b + jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    in_range = row < n_total
    is_pos = (lab == 1) & in_range
    is_neg = (lab == 0) & in_range

    @pl.when(phase == 0)
    def _reduce():
        stats[0] = jnp.minimum(stats[0], jnp.min(jnp.where(is_neg, d, BIG)))
        stats[1] = jnp.maximum(stats[1], jnp.max(jnp.where(is_pos, d, -BIG)))

    @pl.when(phase == 1)
    def _loss():
        min_neg = stats[0]
        max_pos = stats[1]
        hard_pos = is_pos & (d > min_neg)
        hard_neg = is_neg & (d < max_pos)
        stats[2] += jnp.sum(jnp.square(d) * hard_pos.astype(jnp.float32))
        stats[3] += jnp.sum(jnp.square(jnp.maximum(margin - d, 0.0)) *
                            hard_neg.astype(jnp.float32))

    @pl.when((phase == 1) & (jb == nb - 1))
    def _done():
        out_ref[0] = stats[2]
        out_ref[1] = stats[3]
        out_ref[2] = stats[0]
        out_ref[3] = stats[1]


@functools.partial(jax.jit, static_argnames=("margin", "block_b", "interpret"))
def contrastive_components(e1, e2, labels, margin: float = 0.5, *,
                           block_b: int = 1024, interpret: bool):
    """e1, e2: (B, D); labels: (B,) int -> (pos_loss, neg_loss, min_neg,
    max_pos) as a (4,) float32 vector, matching ref.contrastive_components."""
    B, D = e1.shape
    bb = min(block_b, B)
    nb = -(-B // bb)
    pad = nb * bb - B
    if pad:
        e1 = jnp.pad(e1, ((0, pad), (0, 0)))
        e2 = jnp.pad(e2, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)

    fn = pl.pallas_call(
        functools.partial(_kernel, margin=margin, block_b=bb, n_total=B),
        grid=(2, nb),
        in_specs=[
            pl.BlockSpec((bb, D), lambda p, j: (j, 0)),
            pl.BlockSpec((bb, D), lambda p, j: (j, 0)),
            pl.BlockSpec((bb,), lambda p, j: (j,)),
        ],
        out_specs=pl.BlockSpec((4,), lambda p, j: (0,)),
        out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
        scratch_shapes=[pltpu.SMEM((4,), jnp.float32)],
        interpret=interpret,
    )
    out = fn(e1, e2, labels.astype(jnp.int32))
    return out[0], out[1], out[2], out[3]
