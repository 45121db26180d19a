"""The LLM cell's pieces on the CPU: its configuration file against the
program's registered config, the operation and byte counts, the
readers of its per-layer metrics on hand-made contexts, and tiny runs
in which the check passes the program, fails its fp8 control and fails
a planted fault."""
import json
import time

import jax
import numpy as np
import pytest

import control
import run
from harness import cells, device
from harness.jamba_weights import jamba_shapes, jamba_sizes
from harness.peaks import PEAKS
from ops.cascade import least_seconds
from ops.jamba import generation_flops, param_count, step_cost
from tiny import ROOT
from tiny_llm import tiny_llm_cell

CELL = "chat.jamba2.gen64"
FILE = json.loads((ROOT / "bench/configs/chat-jamba2-3b.json").read_text())
SYSTEM = cells.load_module("systems", "served_llm")


def _read(name, ctx):
    return cells.load_module("metrics", name, ROOT / "bench").read(ctx)


def test_published_keys_give_the_registered_config():
    """The window's engine runs `get_config("jamba2-3b")` unreduced."""
    from repro.configs import get_config
    cfg = SYSTEM.llm_config(FILE)
    assert cfg == get_config("jamba2-3b")
    assert param_count(jamba_sizes(FILE)) == cfg.param_count() \
        == 3_029_337_472


def test_weights_tree_is_the_programs():
    from repro.models import init_lm, split
    want = jax.tree_util.tree_map(
        lambda s: tuple(s.shape),
        split(init_lm(SYSTEM.llm_config(FILE), abstract=True))[0])
    assert want == jamba_shapes(jamba_sizes(FILE))


def test_decode_step_reads_weights_and_state():
    m = jamba_sizes(FILE)
    c = step_cost(32, m, 88)
    state = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)       # h f32, conv bf16
    kv = 2 * 89 * 2 * 128 * 2                          # 2 layers, k and v
    assert c["bytes"] == 2 * 3_029_337_472 + 32 * (2 * state + kv)
    least = least_seconds(c, PEAKS["TPU v5 lite"])
    assert least == c["bytes"] / 819e9                 # bandwidth-bound
    assert 8.0e-3 < least < 8.3e-3


def test_generation_flops_counts_every_token_run():
    m = jamba_sizes(FILE)
    one = generation_flops(1, 1, m)          # one token, one head
    per_token = one - 2 * 2560 * 65536
    two = generation_flops(1, 2, m)          # a second token, context 2
    extra = 2 * 4 * 20 * 128                 # 2 layers attend one more key
    assert two == 2 * one + extra
    assert generation_flops([5, 7], 64, m) == generation_flops(
        5, 64, m) + generation_flops(7, 64, m)
    assert per_token > 2 * 2.8e9             # ~2 x the non-embedding weights


CTX = {
    "spans": {"request": [0.8, 0.9, 0.7], "generate": [0.75, 0.85, 0.0]},
    "trace": {"programs": {"jit_decode_step": [1.26, 126]}},
    "decode_calls": [(32, 63), (16, 63)],
    "prompt_tokens": [20, 24],
    "new_tokens": 64,
    "context": 88,
    "window_s": 2.0,
    "device_kind": "TPU v5 lite",
}


def _ctx(**kw):
    return dict(CTX, llm=jamba_sizes(FILE), **kw)


def test_generate_ms_is_the_mean_span():
    assert _read("generate_ms", _ctx()) == pytest.approx(1600 / 3)


def test_decode_step_ms_is_device_time_per_execution():
    assert _read("decode_step_ms", _ctx()) == pytest.approx(10.0)


def test_decode_roofline_is_a_steps_least_time_over_its_device_time():
    """Per step on both sides: the mean least time over the window's
    steps (here half at 32 rows, half at 16) over the device time of one
    execution (1.26 s over 126)."""
    m, peak = jamba_sizes(FILE), PEAKS["TPU v5 lite"]
    least = (least_seconds(step_cost(32, m, 88), peak)
             + least_seconds(step_cost(16, m, 88), peak)) / 2
    assert _read("decode_roofline", _ctx()) == pytest.approx(
        100 * least / 0.01)


def test_gen_mfu_counts_real_rows_only():
    flops = generation_flops([20, 24], 64, jamba_sizes(FILE))
    assert _read("gen_mfu", _ctx()) == pytest.approx(
        100 * flops / 2.0 / 197e12)


@pytest.mark.parametrize("name,drop", [
    ("generate_ms", "spans"), ("decode_step_ms", "trace"),
    ("decode_roofline", "trace"), ("decode_roofline", "decode_calls"),
    ("gen_mfu", "trace"), ("gen_mfu", "prompt_tokens")])
def test_none_without_what_it_reads(name, drop):
    """A program without the LLM's spans, or a run without a device
    trace, gives no reading and no error."""
    empty = {"spans": {}, "trace": {}, "decode_calls": [],
             "prompt_tokens": []}[drop]
    assert _read(name, _ctx(**{drop: empty})) is None


def test_the_benchmark_lists_each_reader():
    cell = cells.load(ROOT, CELL)
    assert set(cells.readers(cell)) == {"generate_ms", "decode_step_ms",
                                        "decode_roofline", "gen_mfu"}


def test_control_fails_where_the_program_passes():
    cell = tiny_llm_cell()
    r = control.readings(cell, 2 ** 37 + 5, 1.0)
    limit = cell.config["limits"]["logit_gap"]
    assert r["logit_gap"] <= limit < r["control_logit_gap"]
    assert r["bad_answers"] == r["false_hits"] == r["bad_tokens"] == 0


def _swapped_answers(system, monkeypatch):
    """Each generation's answer handed to the next leader."""
    engine = system.live.engine
    generate = engine.generate

    def swapped(*a, **k):
        res = generate(*a, **k)
        res.tokens = np.roll(res.tokens, 1, axis=0)
        return res
    engine.generate = swapped


def _no_inner_norms(system, monkeypatch):
    """The Mamba mixers' dt/B/C norms left out of the timed path."""
    from dataclasses import replace
    from repro.serving.engine import ServeEngine
    e = system.live.engine
    cfg = e.cfg.replace(ssm=replace(e.cfg.ssm, inner_norms=False))
    ServeEngine.__init__(e, cfg, e.params, e.max_len)


def _tokens_from_the_next_row(system, monkeypatch):
    """Each row fed and answered the token picked from the next row's
    logits: the reference, teacher-forced on the answers, agrees with
    the logits the program computed, so only the picks show it."""
    from repro.serving import engine as mod
    select = mod._select
    monkeypatch.setattr(mod, "_select", lambda *a: jax.numpy.roll(
        select(*a), -1, axis=0))
    e = system.live.engine
    mod.ServeEngine.__init__(e, e.cfg, e.params, e.max_len)


@pytest.mark.parametrize("fault", [_swapped_answers, _no_inner_norms,
                                   _tokens_from_the_next_row],
                         ids=lambda f: f.__name__)
def test_fault_fails_the_check(fault, monkeypatch):
    cell = tiny_llm_cell()
    init = SYSTEM.System.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        fault(self, monkeypatch)
    monkeypatch.setattr(SYSTEM.System, "__init__", broken)
    res = run.run_cell(cell, 2 ** 36 + 9, 1.0, False, time.perf_counter(),
                       device.describe())
    assert not res["correct"], res["checks"]
    if fault is _tokens_from_the_next_row:
        checks = res["checks"]
        assert checks["bad_tokens"]["value"] > 0
        assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
