"""Span tracer: per-request stage timing as a tree (DESIGN.md §10.2).

A *span* is one named, timed region of a request's life
(``embed``/``plan``/``generate``/``commit``/``maintenance``); spans
nest, so one ``CachedLLMService.handle`` call produces one *span tree*
rooted at ``request``.  The tracer is deliberately tiny:

  * ``tracer.span(name, **attrs)`` is a context manager; entering
    pushes onto a plain stack (the serve loop is single-threaded —
    the shadow-rebuild thread never traces), exiting stamps the wall
    time and attaches the span to its parent.
  * Finished *root* spans land in a bounded ring (``keep`` most
    recent), inspectable via ``last_root()`` / ``drain()`` — the unit
    tests assert the full embed->plan->generate->commit tree from
    here, and an operator can dump recent request timelines without
    having wired an exporter.
  * With ``annotate_xla=True`` each span also enters a
    ``jax.profiler.TraceAnnotation``, so when a profiler trace is
    being captured the device work dispatched under a span shows up
    *attributed to that stage* in the XLA timeline (DESIGN.md §10.4).
    Outside an active capture the annotation is a few hundred
    nanoseconds of overhead.
  * Spans are structural; they do **not** write metrics (the serving
    layers observe the ``stage_latency_seconds`` histogram directly,
    exactly once per stage — see DESIGN.md §10.2 for why the two are
    kept separate).
  * Code below the serving pipeline (the cache service, the embedder)
    holds no tracer: ``child(name, **attrs)`` opens a span under
    whatever span is open on the calling thread.  It only ever adds
    children, never a root: with no span open (a direct
    ``CacheService.plan()`` call, or the shadow-rebuild thread) it
    returns the shared no-op context and records nothing.

``NULL_TRACER`` (or ``Tracer(enabled=False)``) makes ``span()`` return
a shared reusable no-op context manager; such a tracer never becomes
the thread's open tracer, so ``child()`` under it costs one lookup.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class Span:
    __slots__ = ("name", "attrs", "start_s", "end_s", "children")

    def __init__(self, name: str, attrs: Dict[str, object]):
        self.name = name
        self.attrs = attrs
        self.start_s = time.perf_counter()
        self.end_s: Optional[float] = None
        self.children: List["Span"] = []

    @property
    def duration_s(self) -> float:
        return (self.end_s or time.perf_counter()) - self.start_s

    def to_dict(self) -> Dict[str, object]:
        """Plain-dict tree (JSON-able)."""
        return {
            "name": self.name,
            "duration_s": self.duration_s,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def walk(self):
        """Pre-order iteration over the tree."""
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str) -> Optional["Span"]:
        for s in self.walk():
            if s.name == name:
                return s
        return None

    def stage_names(self) -> List[str]:
        """Direct children's names in completion order — the stage
        sequence of one request."""
        return [c.name for c in self.children]

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.duration_s * 1e3:.2f}ms, "
                f"{len(self.children)} children)")


class _Open(threading.local):
    """The tracer with a span open on this thread, for `child` (a class
    default: a thread-local's missing attribute costs an exception)."""
    tracer: Optional["Tracer"] = None


_open = _Open()


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_attrs", "_span", "_ann", "_outer")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Dict[str, object]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._span: Optional[Span] = None
        self._ann = None
        self._outer = None

    def __enter__(self) -> Span:
        t = self._tracer
        self._span = span = Span(self._name, self._attrs)
        t._stack.append(span)
        self._outer = _open.tracer
        _open.tracer = t
        if t.annotate_xla:
            self._ann = TraceAnnotation(self._name)
            self._ann.__enter__()
        return span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        t = self._tracer
        span = self._span
        span.end_s = time.perf_counter()
        # unwind to this span even if inner code leaked an open child
        while t._stack and t._stack[-1] is not span:
            t._stack.pop()
        if t._stack:
            t._stack.pop()
        if t._stack:
            t._stack[-1].children.append(span)
        else:
            t._roots.append(span)
        _open.tracer = self._outer


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return _NULL_SPAN

    def __exit__(self, *exc) -> None:
        pass


class _NullSpan:
    __slots__ = ()
    name = ""
    attrs: Dict[str, object] = {}
    duration_s = 0.0
    children: List[Span] = []

    def to_dict(self):
        return {}


_NULL_SPAN = _NullSpan()
_NULL_CTX = _NullCtx()


class Tracer:
    def __init__(self, *, enabled: bool = True, annotate_xla: bool = False,
                 keep: int = 64):
        """``keep``: finished root spans retained (ring buffer)."""
        self.enabled = bool(enabled)
        self.annotate_xla = bool(annotate_xla)
        self._stack: List[Span] = []
        self._roots: deque = deque(maxlen=keep)

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL_CTX
        return _SpanCtx(self, name, attrs)

    def current(self) -> Optional[Span]:
        return self._stack[-1] if self._stack else None

    def last_root(self) -> Optional[Span]:
        return self._roots[-1] if self._roots else None

    def roots(self) -> List[Span]:
        return list(self._roots)

    def drain(self) -> List[Span]:
        out = list(self._roots)
        self._roots.clear()
        return out


NULL_TRACER = Tracer(enabled=False)


def child(name: str, **attrs):
    """A span named ``name`` under the span open on this thread, in
    that span's tracer (annotated like any of its spans); the shared
    no-op context where no span is open."""
    t = _open.tracer
    if t is None:
        return _NULL_CTX
    return _SpanCtx(t, name, attrs)
