"""From a profiler trace to device busy time, program times and idle gaps.

Two steps, kept apart so the second can be checked on a small recorded
trace without a chip:

* `extract` reads the ``.xplane.pb`` that `jax.profiler` wrote and keeps
  three lists of ``(name, start_ns, duration_ns)``: the device's ops and
  its program (XLA module) executions, per TPU plane, and the host
  annotations of the main thread (the program's spans under
  ``Tracer(annotate_xla=True)`` and the harness's ``bench_window``).
* `reduce_events` turns those lists into the numbers the readers use:
  busy seconds (the union of op intervals inside the window), the
  window's length, device seconds and calls per program (by name, and
  by fingerprint under the host span that was open when each execution
  started: several programs share a name such as ``jit__unknown``,
  which JAX gives every jitted `functools.partial`), and the idle
  time, split by the innermost host span open at each moment of it and
  summed by that span's name.

Host spans and device events are compared on the host's clock.  The
device's clock is pinned to it by the harness's clock marks
(``bench_mark``: a tiny program run inside a host annotation of the
same name, before and after the window): each mark's execution lies
inside its annotation, which bounds the offset between the two clocks
at both ends of the window; the device's events are moved by the least
offset within those bounds, drawn as a line between the two ends (by
0 where the clocks agree).  Without marks they stay as recorded.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns
WINDOW = "bench_window"
MARK = "bench_mark"
MARK_PROGRAM = "jit_bench_mark"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(module_event: str) -> str:
    """``jit_cascade_query(1234)`` -> ``jit_cascade_query``."""
    return _SUFFIX.sub("", module_event)


def extract(trace_dir: str, host_names) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    keep = set(host_names) | {WINDOW, MARK}
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key:
                    dev[key] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [(e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events if e.name in keep]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def clock(host: List[Event], modules: List[Event], lo: float, hi: float):
    """-> (device ns -> host ns, [offset ns before the window, after]).

    Each mark's execution lies inside its annotation, so the offset
    that takes the device's clock to the host's lies between the
    annotation's start less the execution's and its end less the
    execution's.  The marks before the window (``lo``) and those after
    it (``hi``) each give such a range; the offset at each end is the
    one in its range nearest 0 (0 where the clocks agree as recorded),
    and the offset in between lies on the line through the two.  Where
    the marks do not pair up, the events stay as recorded."""
    h = sorted((s, s + d) for n, s, d in host if n == MARK)
    m = sorted((s, s + d) for n, s, d in modules
               if program_name(n) == MARK_PROGRAM)
    ends = []
    for side in (lambda t: t < lo, lambda t: t >= hi):
        pairs = [(a, b) for a, b in zip(h, m) if side(a[0])]
        if len(h) != len(m) or not pairs:
            return (lambda t: t), []
        least = max(a[0] - b[0] for a, b in pairs)
        most = min(a[1] - b[1] for a, b in pairs)
        off = ((least + most) / 2 if least > most else
               min(max(0.0, least), most))
        ends.append((sum(b[0] for _, b in pairs) / len(pairs), off))
    (t0, o0), (t1, o1) = ends
    slope = (o1 - o0) / (t1 - t0) if t1 > t0 else 0.0

    def to_host(t: float) -> float:
        return t + o0 + slope * (t - t0)

    return to_host, [o0, o1]


def _moved(events: List[Event], to_host) -> List[Event]:
    return [(n, to_host(s), d) for n, s, d in events]


class _Spans:
    """Host spans sorted by start, for `innermost` lookups."""

    def __init__(self, host: List[Event]):
        spans = sorted((s, s + d, n) for n, s, d in host
                       if n not in (WINDOW, MARK))
        self.starts = [s for s, _, _ in spans]
        self.spans = spans

    def innermost(self, t: float, depth: int = 16) -> str:
        """The latest-starting span that covers ``t``.  Spans of the
        serving thread nest a few deep, so the answer is among the last
        few that start before ``t``."""
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - depth, -1), -1):
            s, e, n = self.spans[j]
            if e >= t:
                return n
        return "untracked"

    def idle(self, gaps: List[Tuple[float, float]], lo: float,
             hi: float) -> Dict[str, float]:
        """Seconds of ``gaps`` (sorted, apart) inside ``[lo, hi]``, by
        the innermost span open at each moment: the window is cut at
        every span's start and end, and each piece goes to one span."""
        cuts = sorted({lo, hi} | {t for s, e, _ in self.spans
                                  for t in (s, e) if lo < t < hi})
        out: Dict[str, float] = {}
        j = 0
        for a, b in zip(cuts, cuts[1:]):
            while j < len(gaps) and gaps[j][1] <= a:
                j += 1
            k, got = j, 0.0
            while k < len(gaps) and gaps[k][0] < b:
                got += max(0.0, min(b, gaps[k][1]) - max(a, gaps[k][0]))
                k += 1
            if got > 0:
                n = self.innermost((a + b) / 2)
                out[n] = out.get(n, 0.0) + got * 1e-9
        return out


def reduce_events(ev: dict, top: int = 10) -> dict:
    """-> {"window_s", "busy_s", "idle_share", "programs": {name:
    [seconds, calls]}, "by_span": {host span: {program with its
    fingerprint: [seconds, calls]}} (by the span open when each
    execution started), "clock_offsets_ns": {plane: [offset at the
    first mark, at the last]} (empty lists without marks), "device_ops": [[program, seconds]] (the ``top``
    programs by device time), "idle_gaps": [[host span, idle seconds]] (the
    ``top`` spans by idle time on the first chip)} or {} when the trace
    holds no device plane or no window."""
    windows = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not ev["devices"] or not windows:
        return {}
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    busy, programs, by_span, offsets = [], {}, {}, {}
    idle: Dict[str, float] = {}
    spans = _Spans(ev["host"])
    for i, name in enumerate(sorted(ev["devices"])):
        to_host, offsets[name] = clock(ev["host"],
                                       ev["devices"][name]["modules"], lo, hi)
        dev = {k: _moved(v, to_host) for k, v in ev["devices"][name].items()}
        union = _union(_clip([(s, s + d) for _, s, d in dev["ops"]], lo, hi))
        busy.append(sum(e - s for s, e in union) * 1e-9)
        for n, s, d in dev["modules"]:
            if lo <= s < hi:
                for p in (programs.setdefault(program_name(n), [0.0, 0]),
                          by_span.setdefault(spans.innermost(s), {})
                          .setdefault(n, [0.0, 0])):
                    p[0] += d * 1e-9
                    p[1] += 1
        if i == 0:
            edges = [lo] + [x for iv in union for x in iv] + [hi]
            idle = spans.idle([(s, e) for s, e in zip(edges[::2],
                                                       edges[1::2]) if e > s],
                              lo, hi)
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": programs,
        "by_span": by_span,
        "clock_offsets_ns": offsets,
        "device_ops": [[n, p[0]] for n, p in sorted(
            programs.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
