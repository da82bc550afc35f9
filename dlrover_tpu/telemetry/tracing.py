"""Span tracer with cross-RPC parent/child propagation.

A span is one timed operation (``rdzv.join``, ``ckpt.save``,
``node_check``); nesting inside a process rides a ``contextvars``
context variable, and crossing the master↔agent RPC rides the
trace-context field :mod:`dlrover_tpu.common.comm` injects into every
frame — the server attaches the caller's context while dispatching,
so a master-side span opened inside a handler becomes a child of the
agent-side span that issued the RPC.

Every finished span is (1) kept in a bounded in-memory buffer for
in-process consumers/tests, (2) observed into the
``dlrover_span_seconds`` histogram of the global metrics registry,
and (3) emitted as a ``span`` training event when an event log is
configured — which is how cross-process parent/child linkage is
verified end to end.

One name system: a host span (:func:`span`), its profiler annotation
(:func:`annotation`) and a device scope (:func:`device_scope`, the
name a device operation is summed under).

One clock: in a process that has imported jax, every span also enters
a ``jax.profiler.TraceAnnotation`` named ``dlrover.<span name>``
(:func:`annotation`).  It reaches a device trace only while a
profiler session is active (a flag check otherwise) and carries the
wall clock at entry (``wall_ns``), so a reducer can place the event
log's ``ts`` / ``start_ts`` of EVERY process (agent and master
included) on the profiler's clock from any one span.
"""

import contextvars
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from dlrover_tpu.telemetry import events as _events
from dlrover_tpu.telemetry import metrics as _metrics

TRACE_ID_KEY = "trace_id"
SPAN_ID_KEY = "span_id"
# how a launch's trace crosses a process spawn (no RPC exists yet):
# the parent writes its current context, ``<trace_id>:<span_id>``,
# into the environment it builds for the child anyway (tpurun for the
# local master, the agent for every worker, beside
# ``DLROVER_RECOVERY_T0``).  Never set by a user.
TRACE_PARENT_ENV = "DLROVER_TRACE_PARENT"


@dataclass(frozen=True)
class SpanContext:
    trace_id: str
    span_id: str


_current_span: "contextvars.ContextVar[Optional[SpanContext]]" = (
    contextvars.ContextVar("dlrover_current_span", default=None)
)


# Where the spans that finish INSIDE another span of this process
# wait for it: the outermost span writes them with itself, in one
# append (a flash save is ~35 spans; one write, not 35).  None outside
# any span.  A thread that continues a trace through
# ``attach_context`` starts a buffer of its own; a thread handed a
# COPY of the context shares the outer span's, which must outlive it.
_span_buffer: "contextvars.ContextVar[Optional[list]]" = (
    contextvars.ContextVar("dlrover_span_buffer", default=None)
)


def _new_id() -> str:
    return os.urandom(8).hex()


ANNOTATION_PREFIX = "dlrover."


def annotation(name: str, wall_ns: int, **stats):
    """An entered ``jax.profiler.TraceAnnotation`` named
    ``dlrover.<name>`` with ``wall_ns`` (``time.time_ns()`` at entry)
    and ``stats`` as its stats, or None.

    jax is looked up in ``sys.modules`` and never imported: the agent
    and the master never touch jax (a process that did would hold the
    chip), so their spans reach the event log only.  Close it with
    ``__exit__(None, None, None)``.  Tracing must never raise into
    the operation it measures."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + name, wall_ns=wall_ns,
            **{k: v for k, v in stats.items() if v is not None},
        )
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001 - a half-imported jax, an
        return None  # older profiler: the event log still has it


# every name :func:`device_scope` was entered under in this process
DEVICE_SCOPES: Set[str] = set()


def device_scope(name: str):
    """``jax.named_scope(name)``, with ``name`` kept in
    :data:`DEVICE_SCOPES`: the one way the package opens a device
    scope.  The name becomes a component of the ``op_name`` of every
    instruction lowered inside (metadata alone: the program is the
    same), which is how a device trace's operations are summed by the
    program's own layers; the set goes into the op-name map beside
    the step's AOT entry (``common/aot_cache.py::save_op_names``) and
    tells a scope (``ssm_norm``) from a flax module's name
    (``block_3``).  Runs while a function is TRACED, never in a step.
    jax is looked up as :func:`annotation` does."""
    DEVICE_SCOPES.add(name)
    jax = sys.modules.get("jax")
    return jax.named_scope(name) if jax else nullcontext()


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None
    start_time: float = 0.0
    end_time: float = 0.0
    status: str = "ok"
    attributes: Dict[str, object] = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return max(0.0, self.end_time - self.start_time)

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attribute(self, key: str, value):
        self.attributes[key] = value


class Tracer:
    def __init__(
        self,
        registry: Optional[_metrics.MetricsRegistry] = None,
        max_finished: int = 2048,
    ):
        self._registry = registry or _metrics.get_registry()
        self._duration_hist = self._registry.histogram(
            "dlrover_span_seconds", "Span durations by span name"
        )
        self._finished: "deque[Span]" = deque(maxlen=max_finished)
        self._lock = threading.Lock()
        # push exporters (OTLP) subscribe here instead of patching
        # instrumentation sites: every finished span is handed to each
        # listener, failures swallowed (telemetry must never raise)
        self._listeners: List = []

    def add_listener(self, fn):
        """Register ``fn(span)`` called once per finished span."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn):
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    @contextmanager
    def span(self, name: str, **attributes):
        parent = _current_span.get()
        trace_id = parent.trace_id if parent else _new_id()
        wall_ns = time.time_ns()
        s = Span(
            name=name,
            trace_id=trace_id,
            span_id=_new_id(),
            parent_id=parent.span_id if parent else None,
            start_time=wall_ns / 1e9,
            attributes=dict(attributes),
        )
        # (the profiler reads a stat that looks like a number as one:
        # an all-digit hex id would come back an int, its leading
        # zeros gone; the "s" keeps the join key a string)
        ann = annotation(
            name, wall_ns, step=attributes.get("step"),
            span_id="s" + s.span_id,
        )
        token = _current_span.set(s.context)
        outer = _span_buffer.get()
        inner = _span_buffer.set([]) if outer is None else None
        try:
            yield s
        except BaseException as e:
            s.status = "error"
            s.attributes.setdefault("error", repr(e))
            raise
        finally:
            _current_span.reset(token)
            s.end_time = time.time()
            if ann is not None:
                ann.__exit__(None, None, None)
            if inner is None:
                self._record(s, outer)
            else:
                finished = _span_buffer.get()
                _span_buffer.reset(inner)
                self._record(s, None, finished)

    def record_span(
        self, name: str, start_time: float, end_time: float,
        **attributes,
    ) -> Span:
        """Record a span whose two clock readings (``time.time()``)
        the caller took itself, as a child of the current span: for
        a stretch that must run no tracing code at all (the agent's
        in-RAM copy under the shm lock, where a trainer is waiting
        for the release).  No profiler annotation."""
        parent = _current_span.get()
        s = Span(
            name=name,
            trace_id=parent.trace_id if parent else _new_id(),
            span_id=_new_id(),
            parent_id=parent.span_id if parent else None,
            start_time=start_time,
            end_time=end_time,
            attributes=dict(attributes),
        )
        self._record(s, _span_buffer.get())
        return s

    def _record(self, s: Span, buffer=None, finished=()):
        """Keep, observe and export one finished span.  Its event
        waits in ``buffer`` (the enclosing span's) when there is one;
        otherwise it is written now, after the ``finished`` spans it
        enclosed."""
        with self._lock:
            self._finished.append(s)
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(s)
            except Exception:  # noqa: BLE001 - exporter bug must not
                pass  # kill the instrumented operation
        try:
            self._duration_hist.observe(s.duration, name=s.name)
        except Exception:  # noqa: BLE001 - telemetry must not raise
            pass
        event = dict(
            # when the span ended, whenever it is written
            ts=s.end_time,
            name=s.name,
            trace_id=s.trace_id,
            span_id=s.span_id,
            parent_id=s.parent_id,
            start_ts=s.start_time,
            duration_s=round(s.duration, 6),
            status=s.status,
            attributes=s.attributes,
        )
        if buffer is not None:
            buffer.append(event)
        else:
            _events.emit_many("span", [*finished, event])

    def finished_spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            spans = list(self._finished)
        if name is not None:
            spans = [s for s in spans if s.name == name]
        return spans

    def clear(self):
        with self._lock:
            self._finished.clear()


_default_tracer: Optional[Tracer] = None
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _default_tracer
    with _default_lock:
        if _default_tracer is None:
            _default_tracer = Tracer()
        return _default_tracer


@contextmanager
def span(name: str, **attributes):
    """``with trace.span("rdzv.join", node_rank=r):`` on the global
    tracer."""
    with get_tracer().span(name, **attributes) as s:
        yield s


def record_span(
    name: str, start_time: float, end_time: float, **attributes
) -> Span:
    """:meth:`Tracer.record_span` on the global tracer."""
    return get_tracer().record_span(
        name, start_time, end_time, **attributes
    )


def current_context() -> Optional[SpanContext]:
    return _current_span.get()


def inject_context() -> Optional[Dict[str, str]]:
    """The wire form comm.py appends to each frame (None when no span
    is active — the common case costs one ContextVar read)."""
    ctx = _current_span.get()
    if ctx is None:
        return None
    return {TRACE_ID_KEY: ctx.trace_id, SPAN_ID_KEY: ctx.span_id}


def export_context(env: Dict[str, str]) -> Dict[str, str]:
    """``env`` (a child process's) with the current span as its
    trace parent, or without one where no span is active."""
    ctx = _current_span.get()
    if ctx is None:
        env.pop(TRACE_PARENT_ENV, None)
    else:
        env[TRACE_PARENT_ENV] = f"{ctx.trace_id}:{ctx.span_id}"
    return env


def inherited_context() -> Optional[Dict[str, str]]:
    """The trace parent this process was spawned under, in the wire
    form :func:`attach_context` takes (None: spawned outside any
    span, or not by this framework)."""
    trace_id, _, span_id = os.environ.get(
        TRACE_PARENT_ENV, ""
    ).partition(":")
    if not (trace_id and span_id):
        return None
    return {TRACE_ID_KEY: trace_id, SPAN_ID_KEY: span_id}


@contextmanager
def attach_context(wire_ctx: Optional[Dict[str, str]]):
    """Server side: adopt the caller's trace context for the scope of
    a handler dispatch, so handler-opened spans become its children.
    Tolerates None/malformed (a no-op) — telemetry must never break
    the control plane."""
    if not isinstance(wire_ctx, dict):
        yield
        return
    trace_id = wire_ctx.get(TRACE_ID_KEY)
    span_id = wire_ctx.get(SPAN_ID_KEY)
    if not (isinstance(trace_id, str) and isinstance(span_id, str)):
        yield
        return
    token = _current_span.set(SpanContext(trace_id, span_id))
    # the caller's span lives elsewhere: spans opened here are
    # written when the outermost of them ends
    buffer = _span_buffer.set(None)
    try:
        yield
    finally:
        _span_buffer.reset(buffer)
        _current_span.reset(token)
