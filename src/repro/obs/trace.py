"""Tracing spans + the bounded in-memory event ring (DESIGN.md §12).

``span(site, **attrs)`` is a context manager timing a host-side region with
``time.monotonic()``; on exit it emits a structured event into a bounded
ring (and an optional JSONL sink). Once jax is imported, a span also enters
``jax.profiler.TraceAnnotation(site)`` for its extent, so a profiler trace
holds the span as a host event of the same name, on the clock of the device
ops it dispatched (with the profiler off that costs one TraceMe check).
``instant(site)`` emits a zero-duration point event. ``trace_event(site)``
is the variant for code that runs at JIT *trace* time (the LMS swap stream
helpers, the DDL bucket builder): it fires once per trace, not once per
execution, so its events are plan-shaped byte accounting with no time
(kind="trace").

An ``Obs`` bundles a ``MetricsRegistry`` with a ring. The module-level
default (``get_obs()``/``configure()``) is what free-standing helpers
record into; components that must not cross-contaminate (several engines in
one process, sequential trainer runs) construct ``Obs()`` — a PRIVATE
registry sharing the GLOBAL ring, so per-component metrics stay isolated
while every span still lands on one unified timeline.

Thread safety: the ring and sink are lock-protected (the checkpointer's
async writer emits from its thread); span nesting depth is tracked
per-thread.
"""
from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import IO, Iterator, List, Optional

from repro.obs.registry import MetricsRegistry
from repro.obs.sites import check_site


@dataclass
class SpanEvent:
    """One timeline event. ``t0``/``dur`` are monotonic seconds; ``kind``
    is "span" (timed region), "instant" (point event), or "trace"
    (JIT-trace-time accounting, no time)."""
    site: str
    t0: float
    dur: float
    kind: str = "span"
    depth: int = 0
    tid: int = 0
    attrs: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"site": self.site, "t0": self.t0, "dur": self.dur,
                "kind": self.kind, "depth": self.depth, "tid": self.tid,
                "attrs": self.attrs}


class TraceRing:
    """Bounded in-memory event ring + optional append-only JSONL sink."""

    def __init__(self, maxlen: int = 8192, jsonl_path: Optional[str] = None):
        self._lock = threading.Lock()
        self._maxlen = maxlen
        self._events: List[SpanEvent] = []
        self._file: Optional[IO[str]] = None
        self.jsonl_path: Optional[str] = None
        if jsonl_path:
            self.set_jsonl(jsonl_path)

    @property
    def maxlen(self) -> int:
        return self._maxlen

    def set_jsonl(self, path: Optional[str]) -> None:
        """(Re)point the JSONL sink; None closes it."""
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None
            self.jsonl_path = path
            if path:
                self._file = open(path, "a")

    def record(self, ev: SpanEvent) -> None:
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._maxlen:
                # drop the oldest half in one slice instead of popping per
                # event — appends stay O(1) amortized
                self._events = self._events[-self._maxlen:]
            if self._file is not None:
                self._file.write(json.dumps(ev.to_dict(), default=str) + "\n")
                self._file.flush()

    def events(self) -> List[SpanEvent]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events = []

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


_annotation = None


def _profiler_annotation(site: str):
    """``jax.profiler.TraceAnnotation(site)`` where jax is already imported
    (resolved once), else a no-op: importing obs never imports jax."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(site)


def profiler_trace(log_dir: str):
    """Run the block under the JAX profiler, writing its trace under
    `log_dir` (host spans and device ops on one clock); '' traces nothing.
    The Python function tracer stays off: it would slow every host call."""
    if not log_dir:
        return contextlib.nullcontext()
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


class Obs:
    """A metrics registry + an event ring, the unit every instrumented
    component holds. ``Obs()`` = private registry, shared global ring."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 ring: Optional[TraceRing] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.ring = ring if ring is not None else get_obs().ring
        self._local = threading.local()

    def _depth(self) -> int:
        return getattr(self._local, "depth", 0)

    @contextlib.contextmanager
    def span(self, site: str, **attrs) -> Iterator[SpanEvent]:
        """Time a host-side region; the event is recorded on exit (even on
        exception) with the nesting depth at entry. The profiler sees the
        site alone; the attrs stay in the ring."""
        check_site(site)
        depth = self._depth()
        self._local.depth = depth + 1
        t0 = time.monotonic()
        ev = SpanEvent(site, t0, 0.0, "span", depth,
                       threading.get_ident(), dict(attrs))
        try:
            with _profiler_annotation(site):
                yield ev
        finally:
            ev.dur = time.monotonic() - t0
            self._local.depth = depth
            self.ring.record(ev)

    def instant(self, site: str, **attrs) -> SpanEvent:
        check_site(site)
        ev = SpanEvent(site, time.monotonic(), 0.0, "instant", self._depth(),
                       threading.get_ident(), dict(attrs))
        self.ring.record(ev)
        return ev

    def trace_event(self, site: str, **attrs) -> SpanEvent:
        """Point event emitted at JIT trace time (fires once per trace, not
        per execution) — byte/plan accounting, no time."""
        check_site(site)
        ev = SpanEvent(site, time.monotonic(), 0.0, "trace", self._depth(),
                       threading.get_ident(), dict(attrs))
        self.ring.record(ev)
        return ev


# ---------------------------------------------------------------------------
# module-level default: one global ring (the unified timeline) + one global
# registry for free-standing helpers (offload/overlap/checkpointer)

_default: Optional[Obs] = None
_default_lock = threading.Lock()


def get_obs() -> Obs:
    global _default
    with _default_lock:
        if _default is None:
            obs = Obs.__new__(Obs)
            obs.registry = MetricsRegistry()
            obs.ring = TraceRing()
            obs._local = threading.local()
            _default = obs
        return _default


def configure(jsonl_path: Optional[str] = None,
              ring_size: Optional[int] = None) -> Obs:
    """Configure the global obs: point the JSONL sink, resize the ring."""
    obs = get_obs()
    if ring_size is not None:
        obs.ring._maxlen = ring_size
    if jsonl_path is not None:
        obs.ring.set_jsonl(jsonl_path or None)
    return obs


def reset() -> Obs:
    """Fresh global registry + empty ring (sink closed). Test isolation."""
    global _default
    with _default_lock:
        if _default is not None:
            _default.ring.set_jsonl(None)
        _default = None
    return get_obs()


def span(site: str, **attrs):
    """Module-level convenience: a span on the global obs."""
    return get_obs().span(site, **attrs)


def instant(site: str, **attrs) -> SpanEvent:
    return get_obs().instant(site, **attrs)


def trace_event(site: str, **attrs) -> SpanEvent:
    return get_obs().trace_event(site, **attrs)
