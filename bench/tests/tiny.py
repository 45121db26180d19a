"""The benchmark's cells shrunk to a size the CPU runs in seconds: the
same systems, traffic generators, checks and readers, at tiny widths."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells  # noqa: E402

TINY_ENCODER = {"num_hidden_layers": 2, "hidden_size": 64,
                "intermediate_size": 96, "num_attention_heads": 2,
                "vocab_size": 1024}
TINY_TIERS = {"hot_capacity": 64, "warm_capacity": 512, "n_clusters": 8,
              "bucket": 128}


def tiny_cell(name: str) -> cells.Cell:
    cell = cells.load(ROOT, name)
    cfg, tp = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    cfg["cache"]["tiering"].update(TINY_TIERS)
    cfg.update(TINY_ENCODER)
    tp.update(prefill_rows=600, prefill_batch=16, probe_rows=64,
              probe_rounds=3, entities=512, sample_rows=32, sample_hits=32,
              batch=min(tp["batch"], 8))
    cell.config, cell.traffic = cfg, tp
    return cell
