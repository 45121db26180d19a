"""The LLM cell shrunk to a size the CPU runs in seconds: the same
system, traffic, checks and readers, with the embedder and the cache at
`tiny`'s sizes and Jamba at small widths over one whole 14-layer
period."""
from __future__ import annotations

import copy

from tiny import ROOT, TINY_ENCODER, TINY_TIERS
from harness import cells

TINY_JAMBA = {"num_hidden_layers": 14, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 1,
              "intermediate_size": 96, "vocab_size": 1024,
              "mamba_d_state": 8, "mamba_dt_rank": 8}


def tiny_llm_cell(name: str = "chat.jamba2.gen64",
                  batch: int = 8) -> cells.Cell:
    cell = cells.load(ROOT, name)
    cfg, tp = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    cfg.update(TINY_JAMBA)
    cfg["embedder"].update(TINY_ENCODER)
    cfg["cache"]["tiering"].update(TINY_TIERS)
    tp.update(prefill_rows=600, prefill_batch=16, probe_rows=64,
              probe_rounds=3, entities=512, sample_rows=32, sample_hits=32,
              batch=batch, max_new_tokens=8, sample_generations=4,
              record_every=1)
    cell.config, cell.traffic = cfg, tp
    return cell
