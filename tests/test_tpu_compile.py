"""Compile-only checks of the served path for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (a
kernel Mosaic cannot lower, an unaligned block, a program that does not
fit).  Every kernel here is compiled with ``interpret=False``.  Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that decided at import
whether its tests exist would hand pytest-xdist workers different tests.
"""
import os
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.cache_service import tiers
from repro.configs import get_config
from repro.kernels.cascade_lookup import kernel as cl_kernel
from repro.kernels.cosine_topk import kernel as ctk_kernel
from repro.models import encode, init_lm, init_lm_state, split
from repro.serving import ServeEngine

D, HOT, WARM, CLUSTERS, BUCKET = 768, 512, 4096, 32, 256


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on chip 0 of a described v5e:2x2 host, with
    the persistent compilation cache off (its entries for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _specs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_full_width_encoder_forward_compiles(chip):
    """modernbert-149m at its published widths, one embed batch."""
    cfg = get_config("modernbert-149m")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (22, 768, 50368)
    params = jax.eval_shape(
        lambda: split(init_lm(cfg, jax.random.PRNGKey(0)))[0])
    compiled = jax.jit(lambda p, t, m: encode(p, cfg, t, m)).lower(
        _specs(params, chip), _spec((64, 24), jnp.int32, chip),
        _spec((64, 24), jnp.int32, chip)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 500e6


@pytest.fixture(scope="module")
def jamba2():
    """(params as shapes, engine) of the LLM behind the cache at its
    published widths, bf16 weights."""
    cfg = get_config("jamba2-3b")
    params = jax.eval_shape(
        lambda: split(init_lm(cfg, jax.random.PRNGKey(0)))[0])
    return params, ServeEngine(cfg, params, max_len=88)


def test_jamba2_decode_step_compiles_and_fits(chip, jamba2):
    """The engine's own 32-row decode step fits one chip's 16 GB, and
    updates its state in place."""
    params, engine = jamba2
    state = jax.eval_shape(lambda: init_lm_state(engine.cfg, 32, 88))
    mem = engine._decode.lower(
        _specs(params, chip), _specs(state, chip),
        _spec((32, 1), jnp.int32, chip), _spec((2,), jnp.uint32, chip),
        0.0).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 6e9
    assert mem.alias_size_in_bytes > 0.25e9          # the donated state
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


def test_jamba2_prefill_keeps_no_scan_tensors(chip, jamba2):
    """The engine's prefill of a 32-row bucket of 24-token prompts: the
    Mamba layers' per-token recurrence holds no f32 (32, 24, 5120, 16)
    scan tensors, so the program's scratch is ~0.25 GB, where the chunked
    scan took ~1.37 GB."""
    params, engine = jamba2
    mem = engine._prefill.lower(
        _specs(params, chip), _spec((32, 24), jnp.int32, chip),
        _spec((32, 24), jnp.bool_, chip), None,
        _spec((2,), jnp.uint32, chip), 0.0).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("Q", [8, 128])
def test_four_op_cascade_compiles(chip, Q, quantized):
    """The served lookup (`cascade_query`, fused=False) at D=768."""
    hot = _specs(jax.eval_shape(lambda: tiers.init_hot(HOT, D)), chip)
    warm = _specs(jax.eval_shape(
        lambda: tiers.init_warm(WARM, D, CLUSTERS, BUCKET)), chip)
    jax.jit(partial(tiers.cascade_query, k=1, n_probe=8, tail=128,
                    fused=False, quantized=quantized)).lower(
        hot, warm, _spec((Q, D), jnp.float32, chip),
        _spec((Q,), jnp.int32, chip),
        _spec((Q,), jnp.float32, chip)).compile()


def test_warm_rebuild_compiles(chip):
    warm = _specs(jax.eval_shape(
        lambda: tiers.init_warm(WARM, D, CLUSTERS, BUCKET)), chip)
    jax.jit(partial(tiers.warm_rebuild, iters=4, seed=0)).lower(
        warm).compile()


@pytest.mark.parametrize("Q,N,k", [(8, 4096, 1), (128, 4096, 2),
                                   (32, 1000, 4)])
def test_cosine_topk_kernel_compiles(chip, Q, N, k):
    """The flat store's lookup kernel lowers through Mosaic at D=768."""
    compiled = jax.jit(partial(ctk_kernel.cosine_topk, k=k,
                               interpret=False)).lower(
        _spec((Q, D), jnp.float32, chip), _spec((N, D), jnp.float32, chip),
        _spec((N,), jnp.bool_, chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True])
def test_cascade_kernel_ivf_gather_is_refused(chip, quantized):
    """Mosaic cannot lower the fused cascade kernel's data-dependent IVF
    gathers; `CacheService` turns this refusal into its fused=True
    error.  When a kernel that lowers replaces it, this test goes."""
    f32, i32 = jnp.float32, jnp.int32
    s = lambda shape, dt=f32: _spec(shape, dt, chip)
    args = (s((8, D)), s((8,), i32), s((8,)), s((HOT, D)),
            s((HOT,), jnp.bool_), s((HOT,), i32), s((HOT,), i32),
            s((WARM, D)), s((WARM,), jnp.bool_), s((WARM,), i32),
            s((WARM,), i32), s((WARM,), i32), s((CLUSTERS, D)),
            s((CLUSTERS, BUCKET), i32), s((), i32), s((), i32),
            s((WARM, D), jnp.int8), s((WARM,)))
    with pytest.raises(ValueError, match="Shape mismatch") as err:
        jax.jit(partial(cl_kernel.cascade_lookup, k=1, n_probe=8, tail=128,
                        quantized=quantized, interpret=False)).lower(*args)
    assert "_gather_lowering_rule" in "".join(
        traceback.format_exception(err.value))
