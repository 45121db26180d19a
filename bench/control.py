#!/usr/bin/env python3
"""Readings of the program and of its control, over many seeds, on the chip.

    python3 bench/control.py --workload chat.repeat --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up and a short window at
the cell's own load, then the check, which reads each compared number
for the program and, beside it, for the control: the plain reference
put in the program's place one precision step down (the embedder's
matrix products in fp8).  One JSON line per seed, then a
summary line: the largest program reading and the smallest control
reading of each number, from which the limits in the configuration
files were set.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402
from harness import cells, device  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    module = cells.load_module("systems", cell.config["system"])
    system = module.System(cell, seed, False)
    run.window(system, seconds)
    system.after_window()
    del system.live
    gc.collect()
    return {k: v for k, (v, _) in system.check(control=True).items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = cells.load(run.ROOT, args.workload)
    device.require_tpu(cell.chips)
    sys.path.insert(0, str(cell.root / "src"))
    run.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds)
        rows.append(r)
        print(json.dumps({"seed": seed, **r}), flush=True)
    summary = {}
    for k in rows[0]:
        vals = [r[k] for r in rows]
        summary[k] = min(vals) if k.startswith("control_") else max(vals)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
