#!/usr/bin/env python3
"""Start the tiered semantic cache on a TPU and check what it serves.

    python3 chip_smoke.py               # one chip: the served path
    python3 chip_smoke.py --four-chips  # four chips: the sharded warm tier

One chip: builds `repro.launch.serve --cache --tiered --no-smoke` through
the launcher's own construction (`serve.build_stack`): the modernbert-149m
embedder at its published widths (22 layers, d_model 768, vocab 50,368,
cache keys D=768, random weights from a seed), fine-tuned for one epoch on
seeded synthetic pairs, in front of the reduced decoder.  It serves a
seeded query stream through `CachedLLMService.handle` until the hot tier
has flushed to the warm ring, the warm IVF has been rebuilt and requests
hit in the warm tier.  Then, for a seeded probe batch, it copies the tier
state to the host and recomputes the four-op cascade on the CPU at f32
("highest" matmul precision): top-1 value ids and hit verdicts must match
the chip's `plan()` except where the CPU score lies within 1e-3 of the
threshold or of the runner-up.  Last, it checks that `fused=True` is
refused on the chip with the compiler's reason.

Four chips: a sharded `CacheService` over `make_cache_mesh(4)`, filled past
a flush and a rebuild, whose `plan()` must equal the single-device oracle
(`tiers._cascade_sharded_oracle`) on the host, and whose warm tier must sit
one shard per device.

It exits non-zero, and prints no result, when JAX finds no TPU or a check
fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NEAR = 1e-3          # CPU-score band around the threshold / runner-up
# The embedder's weights are random: at the paper's threshold 0.93 nearly
# every query of the small medical grammar hits and the hot tier never
# fills.  At 0.99 the hits are repeats and near-repeats, and 2048
# requests in batches of 32 flush the hot tier several times.
REQUESTS, BATCH, THRESHOLD = 2048, 32, 0.99


def tpu_devices(count: int) -> dict:
    """Print the devices JAX sees; refuse anything but `count` TPUs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"platform {d.platform}, device_kind {d.device_kind}, "
          f"devices {len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); this check runs only on a TPU")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def _on_cpu(tree):
    import jax
    return jax.device_put(jax.device_get(tree), jax.devices("cpu")[0])


def serve_phase(argv):
    """Build the launcher's stack, serve its stream, check the counters."""
    from repro.launch import serve
    args = serve.parse_args(argv)
    stack = serve.build_stack(args)
    serve.report(args, stack, serve.serve_stream(args, stack))
    snap = stack.cache.stats_snapshot()
    counters = {"demotions": snap.tiers["demotions"],
                "rebuilds": snap.rebuild["rebuilds"],
                "warm_hits": snap.traffic["warm_hits"],
                "hot_hits": snap.traffic["hot_hits"]}
    print(f"counters: {counters}")
    for name in ("demotions", "rebuilds", "warm_hits"):
        check(counters[name] > 0, f"{name} counter is 0 after "
              f"{args.requests} requests")
    return args, stack


def probe_texts(requests: int) -> list:
    """A seeded probe batch: 16 queries of the served stream (the
    launcher's seed) and 16 fresh ones."""
    from repro.data import make_query_stream
    rng = np.random.default_rng(7)
    served = [q.text for q in make_query_stream(
        "medical", requests, seed=1, repeat_frac=0.4)]
    fresh = [q.text for q in make_query_stream(
        "medical", 16, seed=7, repeat_frac=0.0)]
    return [served[i] for i in rng.choice(len(served), 16,
                                          replace=False)] + fresh


def probe_phase(stack, probe_texts) -> None:
    """Chip plan() vs the four-op cascade recomputed on the CPU at f32."""
    import jax
    import jax.numpy as jnp

    from repro.cache_service import tiers
    from repro.cache_service.protocol import CacheRequest
    from repro.core import store
    from repro.kernels.cosine_topk import ref as topk_ref
    from repro.models import encode

    cache, trainer, tok = stack.cache, stack.trainer, stack.tokenizer
    embs = np.asarray(stack.service.embed_fn(probe_texts), np.float32)
    check(embs.shape == (len(probe_texts), cache.dim)
          and bool(np.isfinite(embs).all()),
          f"probe embeddings {embs.shape} not finite (B, {cache.dim})")

    with jax.default_matmul_precision("highest"):
        ids, mask = tok.encode_batch(list(probe_texts), trainer.ft.max_len)
        e_cpu = np.asarray(jax.jit(
            lambda p, t, m: encode(p, trainer.cfg, t, m))(
                _on_cpu(trainer.params), _on_cpu(ids), _on_cpu(mask)))
    print(f"embeddings: chip vs CPU f32 max |diff| "
          f"{float(np.abs(embs - e_cpu).max()):.3e}")

    qt = np.zeros(len(probe_texts), np.int32)
    thr = np.asarray(cache.policies.effective_thresholds(qt, None),
                     np.float32)
    hot, warm = _on_cpu(cache.hot), _on_cpu(cache.warm)
    plan = cache.plan(CacheRequest.build(embs, qt), coalesce=False)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(partial(tiers.cascade_lookup, k=2,
                              n_probe=cache._n_probe, tail=cache._tail))(
            hot, warm, _on_cpu(embs), _on_cpu(qt), _on_cpu(thr))
    s = np.asarray(ref.scores)
    ref_vid = np.asarray(ref.value_ids[:, 0])
    ref_hit = np.asarray(ref.hit)
    live = s[:, 0] > tiers.NEG / 2
    band = (np.abs(s[:, 0] - thr) < NEAR) \
        | ((s[:, 1] > tiers.NEG / 2) & (s[:, 0] - s[:, 1] < NEAR))
    wrong = (plan.top_value_ids != ref_vid) | (plan.hit != ref_hit)
    print(f"probe: {len(probe_texts)} queries, {int(ref_hit.sum())} hits "
          f"on the CPU reference, {int(plan.hit.sum())} on the chip; "
          f"{int(band.sum())} within {NEAR} of the threshold or runner-up; "
          f"top-1/verdict mismatches {int(wrong.sum())} "
          f"({int((wrong & band).sum())} inside that band)")
    if live.any():
        print(f"scores: chip vs CPU f32 max |diff| "
              f"{float(np.abs(plan.scores[live] - s[live, 0]).max()):.3e}")
    check(not (wrong & ~band).any(),
          f"chip plan() disagrees with the CPU cascade outside the "
          f"{NEAR} band on rows {np.nonzero(wrong & ~band)[0].tolist()}")

    # recall of the tiered cascade against exact tenant-masked brute
    # force over every live row of both tiers (core/store semantics)
    keys = np.concatenate([np.asarray(hot.keys), np.asarray(warm.keys)])
    ok = np.concatenate([np.asarray(hot.valid) & (np.asarray(hot.tenants)
                                                  == 0),
                         np.asarray(warm.valid) & (np.asarray(warm.tenants)
                                                   == 0)])
    vids = np.concatenate([np.asarray(hot.value_ids),
                           np.asarray(warm.value_ids)])
    flat = store.init_store(len(keys), cache.dim)._replace(
        keys=jnp.asarray(keys), valid=jnp.asarray(ok),
        value_ids=jnp.asarray(vids))
    with jax.default_matmul_precision("highest"):
        brute = store.query(_on_cpu(flat), _on_cpu(embs), 0.0, k=1,
                            topk_fn=topk_ref.cosine_topk)
    b_vid = np.asarray(brute.value_ids[:, 0])
    b_hit = np.asarray(brute.scores[:, 0]) >= thr
    print(f"recall@1 vs brute force: "
          f"{int((plan.top_value_ids == b_vid)[live].sum())}/"
          f"{int(live.sum())} probes; at threshold "
          f"{int((plan.hit & (plan.top_value_ids == b_vid))[b_hit].sum())}/"
          f"{int(b_hit.sum())} brute-force hits served")


def fused_phase(dim: int) -> None:
    """fused=True must raise with the chip compiler's reason."""
    from repro.cache_service import CacheConfig, CacheService, TieringConfig
    try:
        CacheService(CacheConfig(dim=dim, tiering=TieringConfig(
            hot_capacity=512, warm_capacity=4096, n_clusters=32,
            bucket=256, fused=True)))
    except NotImplementedError as e:
        print(f"fused kernel refused on the chip, as expected: "
              f"{str(e).splitlines()[0][:400]}")
        return
    raise RuntimeError("chip_smoke: the fused cascade kernel compiled; "
                       "serve through it here and record it")


def four_chip_phase(n: int = 4, dim: int = 768, seed: int = 0) -> None:
    """Sharded warm tier over n devices vs the single-device oracle."""
    import jax

    from repro.cache_service import (
        CacheConfig, CacheService, ShardingConfig, TieringConfig, tiers,
    )
    from repro.cache_service.protocol import CacheRequest
    from repro.launch.mesh import make_cache_mesh

    mesh = make_cache_mesh(n)
    check(mesh.shape["model"] == n, f"mesh {dict(mesh.shape)} is not "
          f"{n} wide")
    cache = CacheService(CacheConfig(
        dim=dim, threshold=0.93,
        tiering=TieringConfig(hot_capacity=512, warm_capacity=4096 * n,
                              n_clusters=32 * n, bucket=256),
        sharding=ShardingConfig(mesh=mesh)))
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    rows = unit(rng.standard_normal((3072, dim)).astype(np.float32))
    for i in range(0, len(rows), 64):
        plan = cache.plan(CacheRequest.build(rows[i:i + 64], 0))
        cache.commit(plan, [f"r{i + j}" for j in range(64)])
    snap = cache.stats_snapshot()
    print(f"sharded fill: {len(rows)} rows, demotions "
          f"{snap.tiers['demotions']}, rebuilds "
          f"{snap.rebuild['rebuilds']}, warm shards {cache.warm_shards}")
    check(snap.tiers["demotions"] > 0 and snap.rebuild["rebuilds"] > 0,
          "the sharded fill never flushed or rebuilt")

    per_dev = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(cache.warm):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n
              and all(s.data.shape[:1] == (1,) for s in shards),
              f"warm leaf {leaf.shape} is not one shard per device: "
              f"{leaf.sharding}")
        for s in shards:
            per_dev[s.device] += s.data.nbytes
    print("warm bytes per device: " + ", ".join(
        f"{d.id}:{b}" for d, b in per_dev.items())
        + f"; hot tier on devices "
        f"{sorted(d.id for d in cache.hot.keys.devices())}")
    check(len(set(per_dev.values())) == 1,
          "warm shards differ in size across devices")
    for d in per_dev:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: bytes_in_use "
              f"{stats.get('bytes_in_use', 'not reported')}")

    probes = np.concatenate([
        unit(rows[rng.choice(len(rows), 24, replace=False)]
             + 0.002 * rng.standard_normal((24, dim)).astype(np.float32)),
        unit(rng.standard_normal((8, dim)).astype(np.float32))])
    qt = np.zeros(len(probes), np.int32)
    thr = np.asarray(cache.policies.effective_thresholds(qt, None),
                     np.float32)
    hot, warm = _on_cpu(cache.hot), _on_cpu(cache.warm)
    plan = cache.plan(CacheRequest.build(probes, qt), coalesce=False)
    with jax.default_matmul_precision("highest"):
        qn = tiers._unit(_on_cpu(probes))
        ref = tiers._cascade_sharded_oracle(
            hot, warm, qn, _on_cpu(qt), _on_cpu(thr), 1, cache._n_probe,
            cache._tail, False, False)
    ref_vid = np.asarray(ref.value_ids[:, 0])
    ref_hit = np.asarray(ref.hit)
    s = np.asarray(ref.scores[:, 0])
    wrong = (plan.top_value_ids != ref_vid) | (plan.hit != ref_hit)
    print(f"sharded plan() vs oracle: {len(probes)} probes, "
          f"{int(plan.hit.sum())} hits (oracle {int(ref_hit.sum())}), "
          f"mismatches {int(wrong.sum())}, max |score diff| "
          f"{float(np.abs(plan.scores - s).max()):.3e}")
    check(not wrong.any(), f"sharded plan() differs from the oracle on "
          f"rows {np.nonzero(wrong)[0].tolist()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded warm tier over 4 chips and "
                         "its single-device oracle")
    opts = ap.parse_args(argv)
    device = tpu_devices(4 if opts.four_chips else 1)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    if opts.four_chips:
        four_chip_phase()
    else:
        args, stack = serve_phase([
            "--cache", "--tiered", "--no-smoke", "--requests",
            str(REQUESTS), "--batch", str(BATCH), "--threshold",
            str(THRESHOLD), "--max-new-tokens", "4"])
        enc = stack.embedder
        check((enc.n_layers, enc.d_model, enc.vocab_size,
               stack.cache.dim) == (22, 768, 50368, 768),
              f"embedder is not at its published widths: {enc}")
        probe_phase(stack, probe_texts(args.requests))
        fused_phase(stack.cache.dim)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
