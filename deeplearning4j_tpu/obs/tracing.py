"""Span-based tracing — nestable, cross-process, Chrome-trace exportable.

The reference stack's operability story (StatsListener → StatsStorage →
web UI, plus the libnd4j graph profiler) stops at per-iteration scalars;
it has no notion of *where inside a step* time went, and nothing that
survives a process boundary.  This module is the TPU-native upgrade:

- :func:`span` opens a nestable span (``fit`` → ``epoch`` → ``step`` →
  ``step.dispatch`` / ``step.read``; ``feed.*`` on the feeder's threads)
  carrying its start and end in Unix nanoseconds, the thread that did
  the work, and attributes.  A span never syncs the device: it covers
  host time only, on the clock a ``jax.profiler`` trace starts from
  (``profile_start_time``), so :func:`obs.profiler.timeline` can lay the
  spans over the device's own events.  Device time comes from that
  trace, never from a span.  (:func:`device_sync` is for code that must
  block anyway, e.g. the serving engine's D2H, and wants the wait
  attributed.)
- Span context (trace id + span id) serializes with :func:`inject` /
  :func:`extract` and propagates to child processes through the
  ``DL4J_TPU_TRACE_CONTEXT`` environment variable, so spans emitted by
  multiprocess/multislice workers (``parallel/launcher.py``,
  ``parallel/dcn_trainer.py``) join the parent trace.
- jax's own compile events (``jax.monitoring``: trace, lowering, backend
  compile, persistent-cache load) become the set-up histograms of
  ``registry.setup_metrics``, always, and ``compile.*`` spans under the
  current span when tracing is on; the listener is installed when this
  module is imported.
- Finished spans export as append-only jsonl
  (:meth:`Tracer.export_jsonl`) and as Chrome-trace JSON
  (:meth:`Tracer.export_chrome_trace`) loadable in ``chrome://tracing``
  or https://ui.perfetto.dev.

Tracing is OFF by default (``config.tracing`` / ``DL4J_TPU_TRACING=1``);
a disabled :func:`span` costs one config read and yields a no-op span.
"""

from __future__ import annotations

import contextvars
import dataclasses
import json
import os
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Any, Iterator, Optional

from deeplearning4j_tpu.config import get_config
from deeplearning4j_tpu.obs.registry import setup_metrics

TRACE_CONTEXT_ENV = "DL4J_TPU_TRACE_CONTEXT"


@dataclasses.dataclass
class SpanContext:
    """The serializable identity of a span — what crosses process
    boundaries (W3C traceparent equivalent, minimal form)."""

    trace_id: str
    span_id: str

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_dict(d: dict) -> "SpanContext":
        return SpanContext(str(d["trace_id"]), str(d["span_id"]))


@dataclasses.dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    start_s: float                       # epoch seconds (export timestamp)
    end_s: Optional[float] = None
    attributes: dict = dataclasses.field(default_factory=dict)
    device_sync_s: float = 0.0           # time blocked on device→host sync
    pid: int = dataclasses.field(default_factory=os.getpid)
    tid: int = dataclasses.field(default_factory=threading.get_ident)
    # Unix nanoseconds: time_ns() at the start, the end a perf_counter_ns
    # difference later, so a span's length never sees the wall clock step
    start_ns: int = 0
    end_ns: Optional[int] = None
    thread: str = dataclasses.field(
        default_factory=lambda: threading.current_thread().name)
    _t0: int = 0                         # perf_counter_ns at start

    @property
    def duration_s(self) -> float:
        if self.end_s is None:
            return 0.0
        return self.end_s - self.start_s

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "start_s": self.start_s, "end_s": self.end_s,
            "start_ns": self.start_ns, "end_ns": self.end_ns,
            "duration_s": self.duration_s,
            "device_sync_s": self.device_sync_s,
            "pid": self.pid, "tid": self.tid, "thread": self.thread,
            "attributes": self.attributes,
        }


class _NullSpan:
    """No-op span handed out when tracing is disabled — same surface, so
    instrumented code never branches on the enable flag."""

    name = ""
    attributes: dict = {}
    device_sync_s = 0.0

    def set_attribute(self, key: str, value: Any) -> None:
        pass

    def context(self) -> None:
        return None


NULL_SPAN = _NullSpan()

_current_span: contextvars.ContextVar[Optional[Span]] = \
    contextvars.ContextVar("dl4j_tpu_current_span", default=None)

# observers notified on every finished span (the flight recorder mirrors
# spans into its ring here); hooks must be cheap and never raise
_span_hooks: list = []


def add_span_hook(hook) -> None:
    """Register ``hook(span)`` to run on every finished span (any
    tracer).  Idempotent per function object."""
    if hook not in _span_hooks:
        _span_hooks.append(hook)


def remove_span_hook(hook) -> None:
    if hook in _span_hooks:
        _span_hooks.remove(hook)


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Tracer:
    """Collects finished spans; exports jsonl and Chrome-trace JSON.

    ``enabled=None`` (the default global tracer) defers to
    ``config.tracing`` at each span start; ``True``/``False`` pins it
    (bench and tests use pinned local tracers).  A remote parent context
    — from ``DL4J_TPU_TRACE_CONTEXT`` or :meth:`set_remote_parent` —
    becomes the parent of root spans, joining this process's spans to
    the launching process's trace."""

    MAX_SPANS = 200_000   # memory bound; beyond it spans are counted, not kept

    def __init__(self, enabled: Optional[bool] = None):
        self._enabled = enabled
        self._lock = threading.Lock()
        self.spans: list[Span] = []
        self.dropped = 0
        self._jsonl_offsets: dict[str, int] = {}   # per-path export high-water
        self._remote_parent: Optional[SpanContext] = None
        raw = os.environ.get(TRACE_CONTEXT_ENV)
        if raw:
            try:
                self._remote_parent = SpanContext.from_dict(json.loads(raw))
            except (ValueError, KeyError, TypeError):
                pass   # malformed context must never break a worker

    @property
    def enabled(self) -> bool:
        if self._enabled is not None:
            return self._enabled
        return bool(get_config().tracing)

    def set_remote_parent(self, ctx: Optional[SpanContext]) -> None:
        self._remote_parent = ctx

    # ------------------------------------------------------------ spans
    def start_span(self, name: str, parent: Optional[SpanContext] = None,
                   attributes: Optional[dict] = None) -> Span:
        if parent is None:
            cur = _current_span.get()
            parent = cur.context() if cur is not None else self._remote_parent
        trace_id = parent.trace_id if parent else _new_id()
        span_id = _new_id()
        # the two clocks read back to back, after the ids are made: what
        # lies between the reads would shift this span's end against its
        # parent's and children's
        start_ns, t0 = time.time_ns(), time.perf_counter_ns()
        return Span(name=name, trace_id=trace_id, span_id=span_id,
                    parent_id=parent.span_id if parent else None,
                    start_s=start_ns / 1e9, start_ns=start_ns, _t0=t0,
                    attributes=dict(attributes or {}))

    def finish_span(self, s: Span) -> None:
        s.end_ns = s.start_ns + (time.perf_counter_ns() - s._t0)
        s.end_s = s.end_ns / 1e9
        self._keep(s)

    def record_span(self, name: str, start_ns: int, end_ns: int,
                    parent: Optional[SpanContext] = None,
                    **attributes: Any) -> Span:
        """Keep a span whose bounds someone else measured (Unix
        nanoseconds): jax's compile events.  ``parent`` as for
        :meth:`start_span`."""
        s = self.start_span(name, parent=parent, attributes=attributes)
        s.start_ns, s.end_ns = start_ns, end_ns
        s.start_s, s.end_s = start_ns / 1e9, end_ns / 1e9
        self._keep(s)
        return s

    def _keep(self, s: Span) -> None:
        with self._lock:
            if len(self.spans) < self.MAX_SPANS:
                self.spans.append(s)
            else:
                self.dropped += 1
        for hook in _span_hooks:
            try:
                hook(s)
            except Exception:
                pass   # telemetry observers must never break the traced code

    def clear(self) -> None:
        with self._lock:
            self.spans = []
            self.dropped = 0
            self._jsonl_offsets = {}

    def find(self, name: str) -> list[Span]:
        with self._lock:
            return [s for s in self.spans if s.name == name]

    # ---------------------------------------------------------- exports
    def export_jsonl(self, path: str) -> str:
        """Append-only span export; repeated calls on the same path write
        only spans finished since the last export (per-path high-water
        mark), so periodic flushing never duplicates records."""
        directory = os.path.dirname(path)
        if directory:
            os.makedirs(directory, exist_ok=True)
        key = os.path.abspath(path)
        with self._lock:
            start = self._jsonl_offsets.get(key, 0)
            spans = list(self.spans[start:])
            self._jsonl_offsets[key] = start + len(spans)
        with open(path, "a") as f:
            for s in spans:
                f.write(json.dumps(s.to_dict(), default=str) + "\n")
        return path

    def export_chrome_trace(self, path: str) -> str:
        """Chrome trace event format (``ph: "X"`` complete events, µs
        timestamps) — open in ``chrome://tracing`` or Perfetto."""
        directory = os.path.dirname(path)
        if directory:
            os.makedirs(directory, exist_ok=True)
        with self._lock:
            spans = list(self.spans)
        events = []
        for s in spans:
            args = {k: v for k, v in s.attributes.items()}
            if s.device_sync_s:
                args["device_sync_ms"] = round(s.device_sync_s * 1e3, 3)
            args["span_id"] = s.span_id
            if s.parent_id:
                args["parent_id"] = s.parent_id
            events.append({
                "name": s.name, "cat": "tpudl", "ph": "X",
                "ts": s.start_s * 1e6, "dur": max(s.duration_s, 0.0) * 1e6,
                "pid": s.pid, "tid": s.tid,
                "args": {k: (v if isinstance(v, (int, float, str, bool,
                                                 type(None))) else str(v))
                         for k, v in args.items()},
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path


_global_tracer = Tracer()
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    return _global_tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the global tracer (tests / bench pin their own); returns the
    previous one so callers can restore it."""
    global _global_tracer
    with _tracer_lock:
        prev = _global_tracer
        _global_tracer = tracer
    return prev


@contextmanager
def use_tracer(tracer: Tracer) -> Iterator[Tracer]:
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


@contextmanager
def span(name: str, parent: Optional[SpanContext] = None,
         **attributes: Any) -> Iterator[Any]:
    """Open a nested span on the active tracer.  Yields the Span (or a
    no-op when tracing is disabled).  ``parent`` overrides the ambient
    parent — used when hopping threads or processes."""
    tracer = _global_tracer
    if not tracer.enabled:
        yield NULL_SPAN
        return
    s = tracer.start_span(name, parent=parent, attributes=attributes)
    token = _current_span.set(s)
    try:
        yield s
    finally:
        _current_span.reset(token)
        tracer.finish_span(s)


def current_span() -> Optional[Span]:
    return _current_span.get()


def current_context() -> Optional[SpanContext]:
    s = _current_span.get()
    if s is not None:
        return s.context()
    return _global_tracer._remote_parent


def self_intervals(spans) -> list:
    """Each finished span's own time as ``(start_ns, end_ns, thread,
    name)``, Unix nanoseconds: its interval less what its children on the
    same thread cover (a child on another thread, the feeder's, does work
    of its own).  A thread is then in at most one interval at any instant,
    its innermost span's, so summing them by name never counts time twice.
    The name says where the span sat: ``step>step.read``,
    ``feed.stage>retry_attempt``, the parent first where it ran on the
    same thread.  ``spans`` are :class:`Span`\\ s or their ``to_dict()``
    forms."""
    spans = [s if isinstance(s, dict) else s.to_dict() for s in spans]
    by_id = {s["span_id"]: s for s in spans}
    kids: dict = {}
    for s in spans:
        if s.get("end_ns"):
            kids.setdefault((s["parent_id"], s["tid"]), []).append(s)
    out = []
    for (parent_id, tid), group in kids.items():
        parent = by_id.get(parent_id)
        prefix = (parent["name"] + ">"
                  if parent is not None and parent["tid"] == tid else "")
        for s in group:
            name, cur = prefix + s["name"], s["start_ns"]
            for k in sorted(kids.get((s["span_id"], tid), ()),
                            key=lambda k: k["start_ns"]):
                if k["start_ns"] > cur:
                    out.append((cur, k["start_ns"], s["thread"], name))
                cur = max(cur, k["end_ns"])
            if cur < s["end_ns"]:
                out.append((cur, s["end_ns"], s["thread"], name))
    return out


# ------------------------------------------------------ compile events
# jax reports each part of a compile as it happens (``jax.monitoring``):
# a scalar holding the start time when it starts tracing a jitted
# function to a jaxpr, lowering one to a module, or compiling a module
# (or fetching it from the persistent cache), and a time span of
# ``time.time()`` bounds when that ends, each with the function's name;
# and a cached executable's read, deserialize and load as a duration,
# inside the compile's span.  One listener turns them into the set-up
# histograms of ``registry.setup_metrics`` (always) and ``compile.*``
# spans (tracing on).  A steady step compiles nothing and fires nothing.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_XLA_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COMPILE_SPANS = {_TRACE_EVENT: "compile.trace",
                  _LOWER_EVENT: "compile.lower",
                  _XLA_EVENT: "compile.xla"}


class _CompileState(threading.local):
    """One thread's open compile events, outermost first; the cache loads
    inside its outermost backend compile; and, while the thread runs work
    that owns its compiles (:func:`owned_compiles`), that work's tracer."""

    def __init__(self):
        self.open: list = []
        self.loads: list = []            # (end_ns, seconds)
        self.owner: Optional[Tracer] = None


_compiles = _CompileState()


def _compile_started(event: str, _start: float, **_) -> None:
    if event in _COMPILE_SPANS:
        _compiles.open.append(event)


def _cache_loaded(event: str, seconds: float, **_) -> None:
    # jax fetches from its cache only inside a backend compile's event; in
    # a nested one the load's time is the outer event's
    if event == _CACHE_LOAD_EVENT and _compiles.open == [_XLA_EVENT]:
        _compiles.loads.append((time.time_ns(), seconds))


def _compile_ended(event: str, start: float, end: float,
                   fun_name: str = "", **_) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    state = _compiles
    if state.open and state.open[-1] == event:
        state.open.pop()
    if state.open:
        # inside another compile event of this thread (an inner jit's
        # trace inside the outer one's): its time is the outer event's
        return
    loads, state.loads = state.loads, []
    _compile_done(name, int(start * 1e9), int(end * 1e9), fun_name, loads)


def _compile_done(name: str, start_ns: int, end_ns: int, program: str,
                  loads: list) -> None:
    """One outermost compile event: observed in its histogram unless the
    thread's work owns its compiles, and kept as a span (its cache loads
    as children, so that the span's own time is the observation) where
    the thread's tracer is on."""
    state = _compiles
    if state.owner is None:
        metrics = setup_metrics()
        loaded = sum(seconds for _, seconds in loads)
        own = getattr(metrics, name[len("compile."):])   # trace, lower, xla
        own.observe(max((end_ns - start_ns) / 1e9 - loaded, 0.0))
        for _, seconds in loads:
            metrics.cache_load.observe(seconds)
    tracer = state.owner if state.owner is not None else _global_tracer
    if not tracer.enabled or end_ns <= start_ns:
        return
    attributes = {"program": program} if program else {}
    s = tracer.record_span(name, start_ns, end_ns, **attributes)
    for load_end, seconds in loads:
        tracer.record_span("compile.cache_load",
                           max(load_end - int(seconds * 1e9), start_ns),
                           min(load_end, end_ns), parent=s.context(),
                           **attributes)


@contextmanager
def owned_compiles(name: str, tracer: Optional[Tracer] = None,
                   parent: Optional[SpanContext] = None,
                   **attributes: Any) -> Iterator[Any]:
    """Run the block as work whose compiles are its own cost, not the
    process's set-up (the cost model's analysis): jax's compile events on
    this thread inside it go into none of the compile histograms and,
    where ``tracer`` (default: the global one) is on, become children of a
    span ``name`` kept there under ``parent`` (default: the current
    span).  Yields that span, or a no-op one."""
    state = _compiles
    tracer = tracer if tracer is not None else _global_tracer
    s, token = None, None
    if tracer.enabled:
        s = tracer.start_span(name, parent=parent, attributes=attributes)
        token = _current_span.set(s)
    prev, state.owner = state.owner, tracer
    try:
        yield s if s is not None else NULL_SPAN
    finally:
        state.owner = prev
        if s is not None:
            _current_span.reset(token)
            tracer.finish_span(s)


_listening = False


def _install_compile_listener() -> None:
    """Register the compile-event listeners with ``jax.monitoring`` once
    a process (done when this module is imported)."""
    global _listening
    import jax.monitoring as monitoring
    with _tracer_lock:
        if _listening:
            return
        _listening = True
        monitoring.register_scalar_listener(_compile_started)
        monitoring.register_event_duration_secs_listener(_cache_loaded)
        monitoring.register_event_time_span_listener(_compile_ended)


_install_compile_listener()


# ------------------------------------------------------ wire propagation
def inject() -> Optional[str]:
    """Serialize the current span context for the wire (env var, pickle,
    socket header); None when there is no active span."""
    ctx = current_context()
    return json.dumps(ctx.to_dict()) if ctx else None


def extract(raw: Optional[str]) -> Optional[SpanContext]:
    """Inverse of :func:`inject`; tolerant of absent/malformed input."""
    if not raw:
        return None
    try:
        return SpanContext.from_dict(json.loads(raw))
    except (ValueError, KeyError, TypeError):
        return None


def propagation_env() -> dict:
    """Env-var fragment that joins a child process to the current trace
    (picked up by the child's Tracer at import)."""
    raw = inject()
    if raw is None:
        return {}
    return {TRACE_CONTEXT_ENV: raw, "DL4J_TPU_TRACING": "1"}


# ------------------------------------------------------ device helpers
def device_sync(value: Any) -> Any:
    """Block until ``value`` (a jax array / pytree) is ready, attributing
    the wait to the current span's ``device_sync_s``.  This is how spans
    separate host-side dispatch from device execution under jax's async
    dispatch — without it, step wall time hides inside whichever later
    call happens to block first."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(value)
    dt = time.perf_counter() - t0
    s = _current_span.get()
    if s is not None:
        s.device_sync_s += dt
    return out


def device_memory_stats(device=None) -> Optional[dict]:
    """Per-device HBM telemetry (``memory_stats()``) — ``bytes_in_use``,
    ``bytes_limit``, ``peak_bytes_in_use`` where the backend reports them
    (TPU does; CPU returns None)."""
    import jax
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    return dict(stats) if stats else None
