"""Nestable wall-clock tracing with Chrome-trace export.

The repo's timing story in one place: every phase worth watching (an STKDE
build's validation, bucketing, dispatch and output check, serve
prefill/decode, train steps, benchmark reps) opens a ``span``. Spans nest
per thread, carry free-form attributes, and export to the Chrome
trace-event JSON format (load in ``chrome://tracing`` or Perfetto).
Optionally each span also mirrors into ``jax.profiler.TraceAnnotation`` so
host spans line up with device traces when a JAX profile is being captured.

Once JAX is imported, its compile events are added to the innermost span
open on the thread that compiled: ``compiles`` counts backend compiles (a
persistent-cache fetch included) and ``compile_s`` sums the seconds of
tracing, lowering and compiling.

A tracer keeps its newest ``MAX_SPANS`` closed spans and counts the older
ones it dropped (``Tracer.dropped``), so a long session stays bounded.

Naming convention (see docs/observability.md): dotted lowercase
``component.subject[.phase]`` — e.g. ``stkde.pd.dispatch``,
``serve.prefill``, ``train.step``, ``bench.table3.pb_sym``.

Dependency-free: stdlib only; jax is touched lazily: by mirroring, and by
the compile listener once something else has imported jax.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional

_NS_PER_US = 1_000
MAX_SPANS = 65_536        # closed spans a tracer keeps; older ones drop

# jax.monitoring duration events of a compile; the backend one counts as
# one compile (it includes a persistent-cache retrieval)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
)


@dataclasses.dataclass
class Span:
    """One closed (or still-open) traced region."""

    name: str
    start_ns: int                     # relative to the tracer epoch
    duration_ns: Optional[int] = None
    tid: int = 0
    span_id: int = 0
    parent_id: Optional[int] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return 0.0 if self.duration_ns is None else self.duration_ns / 1e9

    def set(self, **attrs) -> "Span":
        """Attach attributes after the span opened (e.g. computed counts)."""
        self.attrs.update(attrs)
        return self

    def to_event(self, pid: int) -> Dict[str, Any]:
        """Chrome trace-event ("X" complete event, microsecond clock)."""
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.start_ns / _NS_PER_US,
            "dur": (self.duration_ns or 0) / _NS_PER_US,
            "pid": pid,
            "tid": self.tid,
            "args": {k: _jsonable(v) for k, v in self.attrs.items()},
        }


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class Tracer:
    """Thread-safe span recorder.

    One process-global instance (``get_tracer()``) backs the module-level
    ``span`` helper; independent instances can be created for tests.
    """

    def __init__(self, mirror_jax: bool = False):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._spans: Deque[Span] = collections.deque(maxlen=MAX_SPANS)
        self._foreign: List[Dict[str, Any]] = []   # ingested child events
        self._next_id = 0
        self.dropped = 0          # closed spans pushed out by the cap
        self.mirror_jax = mirror_jax
        self.epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------- spans
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        if not _compile_listener_on and "jax" in sys.modules:
            _listen_for_compiles()
        with self._lock:
            self._next_id += 1
            sid = self._next_id
        stack = self._stack()
        sp = Span(
            name=name,
            start_ns=time.perf_counter_ns() - self.epoch_ns,
            tid=threading.get_ident(),
            span_id=sid,
            parent_id=stack[-1].span_id if stack else None,
            attrs=dict(attrs),
        )
        stack.append(sp)
        mirror = self._jax_annotation(name) if self.mirror_jax else None
        if mirror is not None:
            mirror.__enter__()
        try:
            yield sp
        finally:
            if mirror is not None:
                mirror.__exit__(None, None, None)
            stack.pop()
            sp.duration_ns = (
                time.perf_counter_ns() - self.epoch_ns - sp.start_ns
            )
            with self._lock:
                if len(self._spans) == self._spans.maxlen:
                    self.dropped += 1
                self._spans.append(sp)

    @staticmethod
    def _jax_annotation(name: str):
        try:
            import jax

            return jax.profiler.TraceAnnotation(name)
        except Exception:
            return None

    # ----------------------------------------------------------- exports
    def spans(self, name: Optional[str] = None) -> List[Span]:
        """Closed spans, optionally filtered by exact name."""
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def to_chrome_trace(self) -> Dict[str, Any]:
        pid = os.getpid()
        with self._lock:
            events = [s.to_event(pid) for s in self._spans]
            events += [dict(e) for e in self._foreign]
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)

    def export_events(self) -> List[Dict[str, Any]]:
        """Chrome events for cross-process merge (see ``ingest``)."""
        return self.to_chrome_trace()["traceEvents"]

    def ingest(self, events: List[Dict[str, Any]],
               pid: Optional[int] = None) -> None:
        """Merge Chrome events produced by another process (e.g. the
        8-device test subprocess) into this tracer's timeline."""
        with self._lock:
            for e in events:
                e = dict(e)
                if pid is not None:
                    e["pid"] = pid
                self._foreign.append(e)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._foreign.clear()
            self._next_id = 0
            self.dropped = 0
        self.epoch_ns = time.perf_counter_ns()


_TRACER = Tracer()
_compile_listener_on = False


def _on_duration(event: str, secs: float, **_) -> None:
    """Add a JAX compile event to the innermost span the global tracer
    has open on the thread that compiled."""
    if event not in COMPILE_EVENTS:
        return
    stack = _TRACER._stack()
    if not stack:
        return
    sp = stack[-1]
    if event == BACKEND_COMPILE:
        sp.attrs["compiles"] = sp.attrs.get("compiles", 0) + 1
    sp.attrs["compile_s"] = sp.attrs.get("compile_s", 0.0) + secs


def _listen_for_compiles() -> None:
    """Register ``_on_duration`` with ``jax.monitoring``, once."""
    global _compile_listener_on
    with _TRACER._lock:
        if _compile_listener_on:
            return
        _compile_listener_on = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, **attrs):
    """Open a span on the process-global tracer (context manager)."""
    return _TRACER.span(name, **attrs)


def set_mirror_jax(on: bool) -> None:
    """Mirror spans into ``jax.profiler.TraceAnnotation`` (device traces)."""
    _TRACER.mirror_jax = on


def save_chrome_trace(path: str) -> None:
    _TRACER.save(path)


def reset() -> None:
    _TRACER.clear()
