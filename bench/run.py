#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload chat.repeat --seed 7 --seconds 10 --trace 0

The cell (an entry of ``BENCHMARK.json``) names a configuration and a
traffic mix; both are files under ``bench/``.  One run, in one process:

1. set-up: the system under test is built from the configuration, with
   its inputs and weights made from ``--seed``; the cache is filled and
   every shape the window uses is run once (JAX's persistent
   compilation cache lives in ``.jax_cache`` inside the checkout, so
   only a checkout's first run compiles);
2. the window: a closed loop of requests for ``--seconds`` seconds,
   each timed from the call to its answers on the host; with
   ``--trace 1`` under the profiler, with the program's spans
   annotated;
3. after the window: the recall probe and the read-back of the last
   committed rows, then the program's state is freed and the plain
   reference checks a seeded sample of what the window served.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
check compared, beside its limit (also the last lines of standard
error).  Without a TPU, or with fewer chips than the cell asks for, it
exits 1 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# one process with few threads: the host's BLAS and OpenMP pools get one
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import cells, device  # noqa: E402
from harness.stats import percentile  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace" / str(os.getpid())


def enable_compile_cache() -> str:
    """The program's own cache directory (``.jax_cache`` in the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), with every program
    kept, however fast it compiles: the window's small insert programs
    would otherwise compile again in every run."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CompileCounter:
    """Counts XLA compilations (not cache loads) while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


def _cpu() -> str:
    """The CPU the main thread last ran on."""
    with open("/proc/thread-self/stat") as f:
        return f.read().rsplit(")", 1)[1].split()[36]


def bench_mark(x):
    return x + 1


class ClockMarks:
    """A tiny program run inside a host annotation of the same name.
    In a trace, each mark's device execution lies inside its host
    annotation, which pins the device's clock to the host's: marks
    before and after the window let the reduction undo the drift
    between the two clocks over the window."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.f = jax.jit(bench_mark)
        self.x = jnp.zeros((8, 128), jnp.float32)
        self.f(self.x).block_until_ready()

    def __call__(self, n: int = 3):
        import jax
        from harness.trace import MARK
        for _ in range(n):
            with jax.profiler.TraceAnnotation(MARK):
                self.f(self.x).block_until_ready()


def window(system, seconds: float):
    """Closed loop for ``seconds``: -> (latencies, queries, elapsed)."""
    lat, n = [], 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while time.perf_counter() < deadline:
        dt, q = system.step()
        lat.append(dt)
        n += q
    return lat, n, time.perf_counter() - t_start


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             dev: dict) -> dict:
    """One run of ``cell``; returns the result object."""
    sys.path.insert(0, str(cell.root / "src"))
    enable_compile_cache()
    import jax
    module = cells.load_module("systems", cell.config["system"])
    system = module.System(cell, seed, trace)
    marks = ClockMarks() if trace else None
    setup_s = time.perf_counter() - t0
    counter = CompileCounter()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        marks()
    counter.on = True
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    with jax.profiler.TraceAnnotation("bench_window"):
        lat, n_queries, elapsed = window(system, seconds)
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    counter.on = False
    events = None
    if trace:
        marks()
        jax.profiler.stop_trace()
        from harness import trace as tr
        events = tr.extract(str(TRACE_DIR), system.span_names)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(f"bench: window {elapsed:.3f} s, {len(lat)} requests of "
          f"{n_queries} queries, {counter.n} compilations inside it; "
          f"process CPU {ru1.ru_utime - ru0.ru_utime:.2f} s user, "
          f"{ru1.ru_stime - ru0.ru_stime:.2f} s system, "
          f"{ru1.ru_nivcsw - ru0.ru_nivcsw} involuntary switches, "
          f"{len(os.listdir('/proc/self/task'))} threads, on CPU "
          f"{_cpu()} of {len(os.sched_getaffinity(0))}", file=sys.stderr)

    dev = dict(dev)
    dev["memory_peak_bytes"] = device.memory_peak_bytes()
    system.after_window()
    ctx = system.layer_context()
    ctx.update(window_s=elapsed, device_kind=dev.get("kind"))
    e2e = {"lookup_qps": n_queries / elapsed,
           "lookup_p95_ms": percentile(lat, 95) * 1e3,
           "hit_recall": system.hit_recall,
           "setup_s": setup_s}
    del system.live
    gc.collect()
    checks = system.check()

    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": n_queries, "failed": system.failed}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from harness import trace as tr
        red = tr.reduce_events(events) if events else {}
        print(f"bench: device clock offsets at the first and last mark, "
              f"ns: {red.get('clock_offsets_ns')}", file=sys.stderr)
        ctx["trace"] = red
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        result["metrics"] = {}
        for name, read in cells.readers(cell).items():
            v = read(ctx)
            if v is not None:
                result["metrics"][name] = {"value": v, "unit": units[name]}
        if red:
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["device"] = dev
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(ROOT, args.workload)
    dev = device.require_tpu(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0,
                      dev)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
