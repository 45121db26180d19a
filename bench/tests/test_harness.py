"""The harness end to end on the CPU at tiny sizes, its refusal to run
without a TPU, and how it finds a cell's pieces by name."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import run
from harness import cells, device
from tiny import ROOT, tiny_cell

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
DEVICE_METRICS = {"serve_mfu", "cascade_roofline", "device_idle"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(name, trace):
    cell = tiny_cell(name)
    res = run.run_cell(cell, 2 ** 35 + 1, 1.0, bool(trace),
                       time.perf_counter(), device.describe())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    got = set(res["metrics"])
    if trace:
        assert not got & DEVICE_METRICS      # no device metric on a CPU
        assert got == want - DEVICE_METRICS
        assert "breakdown" not in res
    else:
        assert got == want
        assert 0 < res["metrics"]["hit_recall"]["value"] <= 1


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chat.repeat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")


def test_pieces_are_found_by_name(tmp_path):
    """A cell, a configuration, a traffic mix and a metric added as files
    and entries are found without editing any harness file."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/chat-modernbert149m.json").read_text())
    (tmp_path / "bench/configs").mkdir(parents=True)
    (tmp_path / "bench/configs/dummy.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "dummy", "source": "x",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "cache service", "moves":
                               "lookup_qps", "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("workloads", "metrics"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "bench/workloads/dummy_mix.json").write_text(
        json.dumps({"generator": "text_queries", "batch": 3}))
    (tmp_path / "bench/metrics/dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = cells.load(tmp_path, "dummy.cell")
    assert cell.config == cfg and cell.traffic["batch"] == 3
    assert [m["name"] for m in cell.per_layer] == ["dummy.metric"]
    assert cells.readers(cell)["dummy.metric"]({}) == 42.0
    assert cells.load_module("systems", cfg["system"]).System
