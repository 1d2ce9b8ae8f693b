"""Query-lifecycle tracing: nestable spans, Chrome-trace export.

Reference analog: the per-operator timing spine of
``operator/OperatorStats.java`` + the request-correlation trace token
of ``server/GenerateTraceTokenRequestFilter.java:29`` — generalized
into Dapper-style spans so one query's life (parse -> bind -> plan ->
program-registry lookup/XLA compile -> per-operator execute ->
exchange -> device sync) is one exportable tree.

Design constraints:

- ~zero cost when disabled: ``span()`` with no active tracer is one
  thread-local read returning a shared no-op context manager — no
  allocation, no clock read.
- thread-safe: spans complete into one list under a lock and carry
  their thread id; nesting is implicit in (tid, t0, dur) containment,
  so concurrent stage threads interleave without corrupting parents.
- stitchable: tracers register process-wide under BOTH the query id
  and the trace token.  A worker task that receives the coordinator's
  ``X-Presto-Trace-Token`` activates ``tracer_for(token)`` — in a
  co-resident process (tests, single-box clusters) that is the SAME
  tracer object, so distributed stages land in one trace.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from presto_tpu.sync import named_lock


class Span:
    """One completed (or in-flight) trace span.  ``t0``/``dur`` are
    ``time.perf_counter()`` based — durations, never wall-clock."""

    __slots__ = ("name", "cat", "t0", "dur", "tid", "args")

    def __init__(self, name: str, cat: str, t0: float, dur: float,
                 tid: int, args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.args = args

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Span({self.name!r}, {self.dur * 1e3:.2f}ms)"


class _NullSpan:
    """Shared no-op context manager: the disabled-tracing fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kwargs):  # matches _LiveSpan.set
        return self


NULL_SPAN = _NullSpan()


class _LiveSpan:
    """Context manager recording one span into its tracer on exit.
    While a ``jax.profiler`` session runs, the span is also a
    ``presto:<name>`` event of its thread in the profile's host plane,
    on the same clock as the device's operations (XProf, Perfetto);
    without a session the annotation costs one flag test."""

    __slots__ = ("_tracer", "name", "cat", "_t0", "_args", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self._args = args
        self._annotation = None

    def set(self, **kwargs):
        """Attach args discovered mid-span (row counts, capacities)."""
        if self._args is None:
            self._args = {}
        self._args.update(kwargs)
        return self

    def __enter__(self):
        self._annotation = TraceAnnotation("presto:" + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        """Ends the span; a second call is a no-op (the runner ends the
        root ``query`` span before the completion event, inside it)."""
        if self._annotation is None:
            return False
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        # StopIteration is generator flow control (the executor wraps
        # page pulls in spans), not a failure worth flagging
        if exc_type is not None and not issubclass(exc_type, StopIteration):
            self.set(error=exc_type.__name__)
        self._tracer._append(
            Span(self.name, self.cat, self._t0, dur,
                 threading.get_ident(), self._args))
        return False


class Tracer:
    """Per-query span collector.

    Completed spans collect into one list under a lock; nesting needs
    no explicit stack — spans record (tid, t0, dur), and containment
    within a thread lane IS the nesting (how Chrome/Perfetto render).

    Bounded: a huge scan emits one span per page pull per operator,
    and the process registry keeps the last ~64 tracers alive — an
    unbounded list would make always-on tracing (query.trace-dir) a
    slow leak on a serving coordinator.  Past ``max_spans`` new spans
    are counted in ``dropped`` instead of retained.
    """

    DEFAULT_MAX_SPANS = 100_000

    def __init__(self, query_id: str, trace_token: Optional[str] = None,
                 max_spans: Optional[int] = None):
        self.query_id = query_id
        self.trace_token = trace_token
        self.t_start = time.perf_counter()
        self.create_time = time.time()  # epoch anchor for export only
        self.spans: List[Span] = []
        self.max_spans = (self.DEFAULT_MAX_SPANS
                          if max_spans is None else max_spans)
        self.dropped = 0
        self._lock = named_lock("trace.Tracer._lock")

    # -- recording ------------------------------------------------------
    def span(self, name: str, cat: str = "engine",
             **args: Any) -> _LiveSpan:
        return _LiveSpan(self, name, cat, args or None)

    def add_complete(self, name: str, cat: str, t0: float, dur: float,
                     **args: Any) -> None:
        """Record a span measured externally (retroactive: e.g. the
        parse that ran before the tracer existed, or an XLA compile
        detected after the fact by the program registry)."""
        self._append(Span(name, cat, t0, dur, threading.get_ident(),
                          args or None))

    def _append(self, s: Span) -> None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return
            self.spans.append(s)

    # -- queries --------------------------------------------------------
    def total_s(self, name: str) -> float:
        """Summed duration of all spans with ``name``.  Note: nested
        same-name spans double count; lifecycle/compile span names are
        non-recursive by construction."""
        with self._lock:
            return sum(s.dur for s in self.spans if s.name == name)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name rollup: {name: {count, total_ms}} — the compact
        span-tree digest the query-log JSONL sink carries."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            spans = list(self.spans)
        for s in spans:
            e = out.setdefault(s.name, {"count": 0, "total_ms": 0.0})
            e["count"] += 1
            e["total_ms"] += s.dur * 1e3
        for e in out.values():
            e["total_ms"] = round(e["total_ms"], 3)
        return out


# ---------------------------------------------------------------------------
# the active tracer (per-thread) + the process-wide trace registry
# ---------------------------------------------------------------------------

_ACTIVE = threading.local()


def current_tracer() -> Optional[Tracer]:
    return getattr(_ACTIVE, "tracer", None)


class _Activation:
    """Context manager binding a tracer to the current thread.  A None
    tracer is a no-op (callers need no branch)."""

    __slots__ = ("_tracer", "_prev")

    def __init__(self, tracer: Optional[Tracer]):
        self._tracer = tracer

    def __enter__(self):
        self._prev = getattr(_ACTIVE, "tracer", None)
        if self._tracer is not None:
            _ACTIVE.tracer = self._tracer
        return self._tracer

    def __exit__(self, *exc):
        if self._tracer is not None:
            _ACTIVE.tracer = self._prev
        return False


def tracing(tracer: Optional[Tracer]) -> _Activation:
    return _Activation(tracer)


def span(name: str, cat: str = "engine", **args: Any):
    """A span under the current thread's tracer — the shared no-op
    when tracing is disabled (one thread-local read)."""
    tr = getattr(_ACTIVE, "tracer", None)
    if tr is None:
        return NULL_SPAN
    return tr.span(name, cat, **args)


# Completed/live tracers, retrievable by query id OR trace token for
# the coordinator's /v1/query/<id>/trace endpoint and for stitching
# worker-side spans into the coordinator's trace.  Bounded: a serving
# process must not accumulate one tracer per query forever — with the
# per-tracer span cap the worst-case retained heap is
# _REGISTRY_MAX/2 tracers x max_spans spans (generated tokens are
# unique, so a tracer usually occupies two keys: ~64 tracers).
_REGISTRY_MAX = 128
_REGISTRY: "collections.OrderedDict[str, Tracer]" = collections.OrderedDict()
_REGISTRY_LOCK = named_lock("trace._REGISTRY_LOCK")


def register(tracer: Tracer) -> Tracer:
    with _REGISTRY_LOCK:
        _REGISTRY[tracer.query_id] = tracer
        _REGISTRY.move_to_end(tracer.query_id)
        token = tracer.trace_token
        if token:
            # first binding wins for the TOKEN key: generated tokens
            # are unique, and when a client deliberately shares one
            # across queries (session-fixed X-Presto-Trace-Token) the
            # token names a correlation context — a later query must
            # not steal the binding mid-flight and corrupt another
            # query's worker-span stitching.  Per-query lookups always
            # work via the query id.
            if token not in _REGISTRY:
                _REGISTRY[token] = tracer
            _REGISTRY.move_to_end(token)
        while len(_REGISTRY) > _REGISTRY_MAX:
            _REGISTRY.popitem(last=False)
    return tracer


def lookup(key: str) -> Optional[Tracer]:
    """Tracer registered under a query id or trace token, if any."""
    with _REGISTRY_LOCK:
        return _REGISTRY.get(key)


def tracer_for(token: str, create: bool = False) -> Optional[Tracer]:
    """The tracer stitching spans for ``token``.  With ``create``,
    a worker that received a token it has never seen (remote
    coordinator) starts a local tracer so its spans are retrievable
    per-node; co-resident processes get the coordinator's own tracer
    and stitch into one trace."""
    tr = lookup(token)
    if tr is None and create:
        tr = register(Tracer(query_id=token, trace_token=token))
    return tr
