"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file, the module that builds and
drives its system under test, its traffic generator and the readers of
its per-layer metrics.

Every piece is a file of its own under ``bench/``, so a later change
adds a cell, a configuration or a metric by adding files and entries:

* ``bench/workloads/<traffic>.json``: a traffic mix's parameters, with
  ``"generator"`` naming a module of ``bench/traffic/``;
* the configuration file that ``BENCHMARK.json`` names, with
  ``"system"`` naming a module of ``bench/systems/``;
* ``bench/metrics/<metric>.py``: one per-layer metric's reader, a
  function ``read(ctx)`` that returns a number or None.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    root: Path
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, name: str, bench_file: str = "BENCHMARK.json") -> Cell:
    root = Path(root)
    bench = json.loads((root / bench_file).read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "workloads" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), root=root, config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_module(kind: str, name: str, base: Path = BENCH):
    """``<base>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    modname = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}" \
        + ("" if Path(base) == BENCH else f"_{abs(hash(str(base)))}")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def readers(cell: Cell) -> Dict[str, object]:
    """Each per-layer metric's ``read`` function, by metric name."""
    return {m["name"]: load_module("metrics", m["name"],
                                   cell.root / "bench").read
            for m in cell.per_layer}
