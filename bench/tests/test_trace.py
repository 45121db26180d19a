"""The trace reduction: busy union, program times and idle time named
by the host span open at the time."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def _events():
    dev = {"ops": [("a", 0, 5), ("b", 15, 3), ("c", 26, 3), ("d", 50, 10),
                   ("e", 52, 4), ("late", 120, 5)],
           "modules": [("jit_cascade_query(7)", 15, 3),
                       ("jit_other(8)", 50, 10), ("jit_late(9)", 120, 5)]}
    host = [("bench_window", 0, 100), ("request", 10, 30), ("plan", 12, 8),
            ("commit", 25, 5)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_programs_and_named_idle():
    red = tr.reduce_events(_events())
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(21e-9)      # nested op not twice
    assert red["idle_share"] == pytest.approx(0.79)
    assert red["programs"] == {"jit_cascade_query": [pytest.approx(3e-9), 1],
                               "jit_other": [pytest.approx(10e-9), 1]}
    assert red["by_span"] == {
        "plan": {"jit_cascade_query(7)": [pytest.approx(3e-9), 1]},
        "untracked": {"jit_other(8)": [pytest.approx(10e-9), 1]}}
    idle = dict(red["idle_gaps"])                 # gaps 5-15, 18-26, 29-50, 60-100
    assert idle["untracked"] == pytest.approx(55e-9)  # 5-10, 40-50, 60-100
    assert idle["request"] == pytest.approx(17e-9)    # 10-12, 20-25, 30-40
    assert idle["plan"] == pytest.approx(5e-9)        # 12-15, 18-20
    assert idle["commit"] == pytest.approx(2e-9)      # 25-26, 29-30
    assert red["device_ops"][0] == ["jit_other", pytest.approx(10e-9)]


def test_marks_undo_a_drifting_device_clock():
    """The device's clock runs 2 ahead at the start and 32 ahead at the
    end: the marks move its events back, so the program that started
    inside ``embed`` is counted there and not under ``plan``."""
    ev = _events()
    host = [("bench_mark", -20, 4), ("bench_window", 0, 100),
            ("embed", 60, 20), ("plan", 80, 10), ("bench_mark", 110, 4)]
    drift = lambda t: t + 2 + 30 * (t + 18) / 130        # noqa: E731
    mods = [("jit_bench_mark(1)", drift(-18), 0),
            ("jit_encode(2)", drift(62), 15),
            ("jit_bench_mark(1)", drift(112), 0)]
    ev = {"devices": {"/device:TPU:0": {"ops": mods, "modules": mods}},
          "host": host}
    red = tr.reduce_events(ev)
    assert red["by_span"] == {"embed": {"jit_encode(2)": [
        pytest.approx(15e-9), 1]}}
    # the least moves that put each mark inside its annotation: the
    # first mark, at -16, already ends its annotation; the last, at 144,
    # has to come back to 114
    assert red["clock_offsets_ns"]["/device:TPU:0"] == [
        pytest.approx(0), pytest.approx(-30)]
    plain = tr.reduce_events({"devices": ev["devices"], "host": [
        h for h in host if h[0] != "bench_mark"]})
    assert list(plain["by_span"]) == ["plan"]      # 62 drifts to 82.5


def test_marks_leave_agreeing_clocks_alone():
    host = [("bench_mark", -20, 4), ("bench_window", 0, 100),
            ("embed", 60, 20), ("plan", 80, 10), ("bench_mark", 110, 4)]
    mods = [("jit_bench_mark(1)", -19, 1), ("jit_encode(2)", 79, 3),
            ("jit_bench_mark(1)", 111, 1)]
    red = tr.reduce_events({"devices": {"/device:TPU:0": {
        "ops": mods, "modules": mods}}, "host": host})
    assert red["clock_offsets_ns"]["/device:TPU:0"] == [0.0, 0.0]
    assert list(red["by_span"]) == ["embed"]


def test_no_device_plane_gives_nothing():
    ev = _events()
    ev["devices"] = {}
    assert tr.reduce_events(ev) == {}


def test_extract_reads_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("plan"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.extract(str(tmp_path), ["plan"])
    names = [e[0] for e in ev["host"]]
    assert tr.WINDOW in names and "plan" in names
    assert ev["devices"] == {}                         # the CPU is no TPU


@pytest.mark.parametrize("name", sorted(p.stem for p in DATA.glob("*.json")))
def test_recorded_chip_trace(name):
    """40 ms of a traced window recorded on a TPU v5e (op names cut to
    40 characters): the cascade runs under ``plan`` and is found there."""
    ev = json.loads((DATA / f"{name}.json").read_text())
    red = tr.reduce_events(ev)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_share"] < 1
    in_plan = red["by_span"]["plan"]
    heaviest = max(in_plan, key=lambda n: in_plan[n][0])
    assert heaviest.startswith("jit__unknown(")
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
