"""The check fails a run whose timed path is broken underneath.

Each test drives a whole tiny run on the CPU with one fault planted in
the system under test once set-up is done, and sees ``correct`` come
out false.  The faults a one-chip cell can have:

* a step that returns its state unchanged: commit stores nothing;
* half of the batch left out: the cascade answers the first half and
  hands those answers to the second half as well;
* an answer altered where it is produced: the embedder's output moved.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import cells, device
from tiny import tiny_cell


def _no_insert(system):
    def insert(hot, embs, *rest):
        return hot, jnp.full(embs.shape[:1], -1, jnp.int32)
    system.live.cache._insert = insert


def _half_batch(system):
    lookup = system.live.cache._lookup

    def half(hot, warm, q, qt, thr):
        res = lookup(hot, warm, q, qt, thr)
        h = (q.shape[0] + 1) // 2
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x[:h], x[:q.shape[0] - h]]), res)
    system.live.cache._lookup = half


def _moved_embedding(system):
    embed = system.live.embed

    def moved(texts):
        e = embed(texts)
        e = e + 0.05 * np.random.default_rng(0).standard_normal(e.shape)
        return (e / np.linalg.norm(e, axis=1, keepdims=True)).astype(
            np.float32)
    system.live.embed = moved


FAULTS = {
    "chat.repeat": [_no_insert, _half_batch, _moved_embedding],
    "chat.single": [_no_insert, _moved_embedding],
}


@pytest.mark.parametrize("name,fault", [
    (c, f) for c, fs in FAULTS.items() for f in fs],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_fails_the_check(name, fault, monkeypatch):
    cell = tiny_cell(name)
    module = cells.load_module("systems", cell.config["system"])
    init = module.System.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        fault(self)
    monkeypatch.setattr(module.System, "__init__", broken)
    res = run.run_cell(cell, 2 ** 36 + 9, 1.0, False, time.perf_counter(),
                       device.describe())
    assert not res["correct"], res["checks"]
