"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Select benches with
``python -m benchmarks.run [fig1 fig2 fig3 table1 fig4 cache kernels]``.
"""
from __future__ import annotations

import sys

from repro.launch.compile_cache import enable_compile_cache


def _registry():
    from benchmarks.paper_benches import (
        bench_ablation_loss, bench_cache_hit_rate, bench_fig1_quora,
        bench_fig2_medical, bench_fig3_forgetting, bench_fig4_latency,
        bench_table1_synthetic,
    )
    from benchmarks.kernel_benches import bench_kernels
    from benchmarks.bench_tiered_cache import bench_tiered_cache
    return {
        "fig1": bench_fig1_quora,
        "fig2": bench_fig2_medical,
        "fig3": bench_fig3_forgetting,
        "table1": bench_table1_synthetic,
        "fig4": bench_fig4_latency,
        "cache": bench_cache_hit_rate,
        "ablation": bench_ablation_loss,
        "kernels": bench_kernels,
        "tiered": bench_tiered_cache,
    }


def main() -> None:
    enable_compile_cache()
    registry = _registry()
    selected = sys.argv[1:] or list(registry)
    unknown = [s for s in selected if s not in registry]
    if unknown:
        raise SystemExit(f"unknown benches {unknown}; have {list(registry)}")
    print("name,us_per_call,derived")
    for key in selected:
        for name, us, derived in registry[key]():
            print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()
