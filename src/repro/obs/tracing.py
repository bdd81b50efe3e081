"""Host-side span tracing: program spans on the profiler's clock, and
phase timing as Chrome trace events.

The device-side telemetry (``obs.telemetry``) answers "what did the BSP
loop do per iteration"; this module answers "where did the wall clock
go" — graph build, partition, compile, dispatch, the serving engine's
flushes and host copies, validate.

  * ``span("serve.flush", args={"kind": "bfs"})`` — a context manager
    that always opens a ``jax.profiler.TraceAnnotation`` (its ``args``
    become the annotation's stats), so the span lands on the host plane
    of any ``jax.profiler`` capture, on the same clock as the device's
    operations. With no profiler running the annotation is inert.
  * ``capture()`` — while one is open, spans are also timed with
    ``time.perf_counter_ns`` into the ambient ``SpanRegistry``
    (``--trace`` on graph_run/graph_serve, ``chip_smoke.py``). Outside a
    capture nothing is recorded, so a long-lived server never grows it.
  * Async-dispatch fencing: JAX returns before the device finishes, so
    a span that should measure execution must fence. Pass the result
    pytree via ``sync=``: ``jax.block_until_ready`` runs INSIDE the
    span, immediately before the end stamp.
  * ``export_chrome_trace(path)`` writes ``{"traceEvents": [...]}``
    with complete ("ph": "X") events, microsecond timestamps.

Span taxonomy (DESIGN.md §10): category "setup" for build/partition/
shard, "compile" for first-trace runs, "dispatch" for steady-state
execution, "validate" for oracle checks, "serve" for serving-loop
phases.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax


@dataclass
class SpanEvent:
    name: str
    category: str
    start_ns: int
    duration_ns: int
    thread_id: int
    args: Dict[str, Any] = field(default_factory=dict)


@dataclass
class SpanRegistry:
    """Accumulates finished spans; thread-safe appends."""

    events: List[SpanEvent] = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def add(self, ev: SpanEvent) -> None:
        with self._lock:
            self.events.append(ev)

    def reset(self) -> None:
        with self._lock:
            self.events.clear()

    def total_ns(self, name: str) -> int:
        return sum(e.duration_ns for e in self.events if e.name == name)

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        pid = os.getpid()
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [
                {"name": e.name, "cat": e.category, "ph": "X",
                 "pid": pid, "tid": e.thread_id,
                 "ts": e.start_ns / 1e3, "dur": e.duration_ns / 1e3,
                 "args": e.args}
                for e in self.events
            ],
        }


_registry = SpanRegistry()
_captures = 0          # open captures; spans record only while > 0
_captures_lock = threading.Lock()


def registry() -> SpanRegistry:
    """The ambient per-process registry ``span()`` records into."""
    return _registry


def reset() -> None:
    _registry.reset()


@contextmanager
def capture():
    """Record spans into the registry while open. The outermost capture
    starts from an empty registry; what it recorded stays readable after
    it closes, until the next one opens."""
    global _captures
    with _captures_lock:
        if _captures == 0:
            _registry.reset()
        _captures += 1
    try:
        yield _registry
    finally:
        with _captures_lock:
            _captures -= 1


@contextmanager
def span(name: str, category: str = "phase",
         args: Optional[Dict[str, Any]] = None, sync=None):
    """Run a block as one span: a profiler annotation always, and a
    registry event while a capture is open. Yields a dict: args the
    block puts there (known only at its end) are added at exit. ``sync``
    is a pytree fenced with ``jax.block_until_ready`` before the end
    stamp (async dispatch would otherwise end the span at enqueue time,
    not completion)."""
    args = dict(args or {})
    late: Dict[str, Any] = {}
    with jax.profiler.TraceAnnotation(name, **args) as annotation:
        t0 = time.perf_counter_ns()
        try:
            yield late
        finally:
            if sync is not None:
                jax.block_until_ready(sync)
            if late:
                annotation.set_metadata(**late)
            if _captures:
                _registry.add(SpanEvent(
                    name=name, category=category, start_ns=t0,
                    duration_ns=time.perf_counter_ns() - t0,
                    thread_id=threading.get_ident(),
                    args={**args, **late}))


def export_chrome_trace(path: str,
                        reg: Optional[SpanRegistry] = None) -> int:
    """Write the registry as Chrome trace-event JSON; returns the event
    count (drivers log it so an empty trace is visible, not silent)."""
    reg = reg if reg is not None else _registry
    obj = reg.to_chrome()
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return len(obj["traceEvents"])
