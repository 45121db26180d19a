import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production mesh and extract memory / cost / roofline artifacts.

THE TWO LINES ABOVE MUST STAY FIRST — jax locks the device count at
first init, and the dry-run needs 512 placeholder host devices for the
2×16×16 multi-pod mesh.  Nothing else (tests, benches) sets this flag.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --arch jamba-1.5-large-398b \
      --shape long_500k --multi-pod

Per run it prints/writes: compiled.memory_analysis() (proves the
per-device footprint), cost_analysis() (FLOPs/bytes for §Roofline), the
collective schedule summary, and the derived roofline terms.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.programs import get_program, prefill_scan_note
from repro.launch.roofline import model_flops, roofline_terms
from repro.launch.sharding import RULE_SETS, sharding_tree


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            rules: str = "train", unroll: bool = True,
            overrides: dict | None = None, constrain_acts: bool = False,
            verbose: bool = True) -> dict:
    """unroll=True: layers unrolled for honest cost_analysis (slow
    compiles) — the single-pod §Roofline pass.  unroll=False: scanned
    layers — fast compiles, used for the multi-pod sharding-proof pass
    (cost numbers would undercount loop bodies, so only memory/compile
    success is recorded).  overrides: ModelConfig.replace kwargs for
    §Perf experiments (e.g. {"attn_f32": False, "loss_chunk": 512})."""
    t0 = time.perf_counter()
    prog = get_program(arch, shape_name, unroll=unroll, overrides=overrides,
                       multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rule_set = RULE_SETS[rules]

    fn = prog.fn
    if constrain_acts:
        from repro.models.actsharding import wrap_with_activation_constraints
        fn = wrap_with_activation_constraints(fn, mesh)

    in_sh = tuple(sharding_tree(a, ax, mesh, rule_set)
                  for a, ax in zip(prog.args, prog.arg_axes))
    out_sds = jax.eval_shape(prog.fn, *prog.args)
    out_sh = sharding_tree(out_sds, prog.out_axes, mesh, rule_set)

    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh,
                          out_shardings=out_sh).lower(*prog.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    terms = roofline_terms(cost, hlo, n_dev)
    mf = model_flops(prog.cfg, prog.shape)
    hlo_total_flops = terms["per_device_flops"] * n_dev
    result = {
        "arch": arch,
        "shape": shape_name,
        "program": prog.name,
        "mesh": list(mesh.shape.values()),
        "multi_pod": multi_pod,
        "rules": rules,
        "unrolled": unroll,
        "overrides": overrides or {},
        "constrain_acts": constrain_acts,
        "config_name": prog.cfg.name,
        "param_count": prog.cfg.param_count(),
        "param_count_active": prog.cfg.param_count(active_only=True),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "peak_estimate_gib": (mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes) / 2**30,
        },
        "roofline": terms,
        "model_flops": mf,
        "hlo_total_flops": hlo_total_flops,
        "useful_flops_ratio": (mf / hlo_total_flops
                               if hlo_total_flops else 0.0),
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
    }
    note = prefill_scan_note(prog)
    if note:
        result["note"] = note
    if verbose:
        print(f"== {arch} × {shape_name} "
              f"({'multi-pod 2x16x16' if multi_pod else 'single-pod 16x16'}, "
              f"rules={rules}) ==")
        print(f"  program={prog.name}  params={result['param_count']:.3e} "
              f"(active {result['param_count_active']:.3e})")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB per device")
        print(f"  cost_analysis: flops/dev={terms['per_device_flops']:.3e} "
              f"bytes/dev={terms['per_device_bytes']:.3e}")
        print(f"  collectives/dev: {terms['per_device_collective_bytes']:.3e} B "
              f"{terms['collective_counts']}")
        print(f"  roofline: compute={terms['t_compute']*1e3:.2f}ms "
              f"memory={terms['t_memory']*1e3:.2f}ms "
              f"collective={terms['t_collective']*1e3:.2f}ms "
              f"-> bottleneck={terms['bottleneck']}")
        print(f"  MODEL_FLOPS/HLO_FLOPS={result['useful_flops_ratio']:.3f}  "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        if note:
            print(f"  note: {note}")
    return result


def run_extrapolated(arch: str, shape_name: str, *, rules: str = "train",
                     multi_pod: bool = False, overrides: dict | None = None,
                     constrain_acts: bool = False,
                     verbose: bool = True) -> dict:
    """Roofline terms for huge-layer-count archs without compiling the
    full unrolled stack: lower 1-period and 2-period variants and scale
    the per-period delta —  X(N) = X(1) + (N-1)·(X(2) - X(1)).
    Exact for layer-linear terms (flops/bytes/collectives of identical
    stacked layers); embed/loss costs live in X(1).  Used only where
    the full unrolled compile is impractical on this 1-core container
    (granite-34b / jamba / qwen train_4k); marked in the output.
    """
    from repro.configs import get_config
    cfg = get_config(arch)
    period = len(cfg.period)
    n = cfg.n_periods

    results = []
    for k in (1, 2):
        sub = cfg.replace(n_layers=k * period, name=f"{cfg.name}-x{k}")
        # build the program directly from the sub-config
        from repro.launch.programs import build_program
        from repro.configs.base import INPUT_SHAPES
        prog = build_program(sub, INPUT_SHAPES[shape_name],
                             overrides=overrides)
        mesh = make_production_mesh(multi_pod=multi_pod)
        rule_set = RULE_SETS[rules]
        fn = prog.fn
        if constrain_acts:
            from repro.models.actsharding import (
                wrap_with_activation_constraints)
            fn = wrap_with_activation_constraints(fn, mesh)
        in_sh = tuple(sharding_tree(a, ax, mesh, rule_set)
                      for a, ax in zip(prog.args, prog.arg_axes))
        out_sds = jax.eval_shape(prog.fn, *prog.args)
        out_sh = sharding_tree(out_sds, prog.out_axes, mesh, rule_set)
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh,
                               out_shardings=out_sh).lower(*prog.args
                                                           ).compile()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        coll = roofline_terms(cost, hlo, mesh.size)
        results.append({
            "flops": coll["per_device_flops"],
            "bytes": coll["per_device_bytes"],
            "coll": coll["per_device_collective_bytes"],
            "args": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
        })

    x1, x2 = results

    def ext(key):
        return x1[key] + (n - 1) * (x2[key] - x1[key])

    from repro.launch.mesh import (
        HBM_BANDWIDTH, ICI_LINK_BANDWIDTH, PEAK_FLOPS_BF16)
    mesh = make_production_mesh(multi_pod=multi_pod)
    prog = get_program(arch, shape_name)  # full cfg, for metadata only
    note = prefill_scan_note(prog)
    terms = {
        "per_device_flops": ext("flops"),
        "per_device_bytes": ext("bytes"),
        "per_device_collective_bytes": ext("coll"),
        "t_compute": ext("flops") / PEAK_FLOPS_BF16,
        "t_memory": ext("bytes") / HBM_BANDWIDTH,
        "t_collective": ext("coll") / ICI_LINK_BANDWIDTH,
        "collective_counts": {},
        "collective_top_ops": [],
        "collective_breakdown": {},
    }
    dom = max(("compute", "memory", "collective"),
              key=lambda k: terms[f"t_{k}"])
    terms["bottleneck"] = dom
    terms["t_bound"] = terms[f"t_{dom}"]
    terms["roofline_fraction"] = (terms["t_compute"] / terms["t_bound"]
                                  if terms["t_bound"] else 0.0)
    mf = model_flops(prog.cfg, prog.shape)
    hlo_total = terms["per_device_flops"] * mesh.size
    result = {
        "arch": arch, "shape": shape_name, "program": prog.name,
        "mesh": list(mesh.shape.values()), "multi_pod": multi_pod,
        "rules": rules, "unrolled": True, "extrapolated": True,
        "overrides": overrides or {}, "constrain_acts": constrain_acts,
        "config_name": prog.cfg.name,
        "param_count": prog.cfg.param_count(),
        "param_count_active": prog.cfg.param_count(active_only=True),
        "memory": {"argument_bytes_per_device": ext("args"),
                   "output_bytes_per_device": 0,
                   "temp_bytes_per_device": ext("temp"),
                   "peak_estimate_gib": (ext("args") + ext("temp")) / 2**30},
        "roofline": terms,
        "model_flops": mf,
        "hlo_total_flops": hlo_total,
        "useful_flops_ratio": mf / hlo_total if hlo_total else 0.0,
        "lower_seconds": 0.0, "compile_seconds": 0.0,
    }
    if note:
        result["note"] = note
    if verbose:
        print(f"== {arch} × {shape_name} (EXTRAPOLATED {n} periods) ==")
        print(f"  roofline: compute={terms['t_compute']*1e3:.2f}ms "
              f"memory={terms['t_memory']*1e3:.2f}ms "
              f"collective={terms['t_collective']*1e3:.2f}ms "
              f"-> bottleneck={dom}")
        print(f"  MODEL/HLO={result['useful_flops_ratio']:.3f} "
              f"args={ext('args')/2**30:.2f}GiB")
        if note:
            print(f"  note: {note}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + ["cache_lookup", None])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned arch × shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--rules", default="train", choices=list(RULE_SETS))
    ap.add_argument("--out", default=None, help="JSON output path prefix")
    ap.add_argument("--scan", action="store_true",
                    help="scanned layers (fast compile; multi-pod pass)")
    ap.add_argument("--attn-bf16", action="store_true",
                    help="§Perf: bf16 attention probs/accumulator")
    ap.add_argument("--param-bf16", action="store_true",
                    help="§Perf: bf16 master weights (serving)")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="§Perf: fused chunked cross-entropy")
    ap.add_argument("--window", type=int, default=0,
                    help="§Perf ablation: sliding-window attention")
    ap.add_argument("--pad-vocab", type=int, default=0,
                    help="§Perf: pad vocab to a shardable multiple")
    ap.add_argument("--pad-experts", type=int, default=0,
                    help="§Perf H7: pad expert count (router-masked)")
    ap.add_argument("--constrain-acts", action="store_true",
                    help="§Perf H6: batch-anchor activation shardings")
    ap.add_argument("--extrapolate", action="store_true",
                    help="1/2-period lower + per-period scaling (for "
                         "88-layer unrolled trains on this container)")
    ap.add_argument("--tag", default="", help="suffix for --out files")
    args = ap.parse_args()
    overrides = {}
    if args.window:
        overrides["sliding_window"] = args.window
    if args.pad_vocab:
        overrides["pad_vocab_to"] = args.pad_vocab
    if args.pad_experts:
        overrides["pad_experts_to"] = args.pad_experts
    if args.attn_bf16:
        overrides["attn_f32"] = False
    if args.param_bf16:
        overrides["param_dtype"] = "bfloat16"
    if args.loss_chunk:
        overrides["loss_chunk"] = args.loss_chunk

    pairs = []
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results, failures = [], []
    for arch, shape in pairs:
        for mp in meshes:
            try:
                if args.extrapolate:
                    r = run_extrapolated(arch, shape, rules=args.rules,
                                         multi_pod=mp,
                                         overrides=overrides or None,
                                         constrain_acts=args.constrain_acts)
                else:
                    r = run_one(arch, shape, multi_pod=mp, rules=args.rules,
                                unroll=not args.scan,
                                overrides=overrides or None,
                                constrain_acts=args.constrain_acts)
                results.append(r)
                if args.out:
                    tag = f"{arch}_{shape}_{'mp' if mp else 'sp'}_{args.rules}"
                    if args.tag:
                        tag += f"_{args.tag}"
                    with open(f"{args.out}_{tag}.json", "w") as f:
                        json.dump(r, f, indent=1)
            except Exception as e:  # a failure here is a sharding bug
                traceback.print_exc()
                failures.append((arch, shape, mp, repr(e)))
    print(f"\n{len(results)} ok, {len(failures)} failed")
    for f in failures:
        print("  FAIL:", f)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
