"""Process-wide engine metrics: counters, gauges, log2 histograms.

Reference analog: the JMX MBean surface of ``presto-main`` (every
operator/memory/exchange bean the jmx connector exposes as tables) —
here one flat registry, fed by the same instrumentation as the span
tracer (obs/trace.py) and queryable via the ``system_metrics`` table
(connectors/system.py).

Everything is process-global on purpose: coordinator executor, worker
task runners and rebuilt executors all account into one place, the
same sharing model as the process-wide program registry.  The
documented counter catalog lives in docs/observability.md; every name
below is pre-registered so ``SELECT * FROM system_metrics`` shows the
full catalog (at zero) even on a fresh process.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from presto_tpu.sync import named_lock


class Counter:
    """Monotonic counter (float-valued so *_seconds totals fit)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount


class Gauge:
    """Point-in-time value: ``set()`` a sample or ``set_fn()`` a
    callback sampled at snapshot time (registry sizes, pool bytes)."""

    __slots__ = ("name", "_value", "_fn")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None

    def set(self, value: float) -> None:
        self._value = float(value)

    def set_fn(self, fn: Callable[[], float]) -> None:
        self._fn = fn

    @property
    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return float("nan")
        return self._value


class Histogram:
    """Fixed log2-bucketed histogram (no per-query allocation, no
    unbounded label space).  Bucket k counts observations with
    ``2^(k-1) < v <= 2^k`` in the histogram's unit; bucket 0 catches
    v <= 1.  32 buckets cover 1ms..49 days when the unit is ms."""

    NUM_BUCKETS = 32

    __slots__ = ("name", "buckets", "count", "total", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.buckets = [0] * self.NUM_BUCKETS
        self.count = 0
        self.total = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        import math

        v = max(float(value), 0.0)
        # ceil, not int: 2.9 belongs in bucket_le_4 (2 < v <= 4), and
        # int() would undercount every value in (2^k, 2^k + 1)
        k = 0 if v <= 1.0 else min(
            self.NUM_BUCKETS - 1, (math.ceil(v) - 1).bit_length())
        with self._lock:
            self.buckets[k] += 1
            self.count += 1
            self.total += v

    def rows(self) -> List[Tuple[str, float]]:
        with self._lock:
            out = [(f"{self.name}.count", float(self.count)),
                   (f"{self.name}.sum", round(self.total, 3))]
            for k, n in enumerate(self.buckets):
                if n:
                    out.append((f"{self.name}.bucket_le_{1 << k}", float(n)))
            count, buckets = self.count, list(self.buckets)
        if count:
            # derived quantiles ride the flat rows so system_metrics and
            # the ?format=json twin carry them; note merge_rows SUMS
            # across nodes — per-node reads are the meaningful ones
            out.extend((f"{self.name}.{p}", v)
                       for p, v in bucket_percentiles(buckets, count).items())
        return out

    def percentiles(self) -> Dict[str, float]:
        """Current p50/p95/p99 upper-bound estimates (doctor evidence)."""
        count, _, buckets = self.snapshot_raw()
        return bucket_percentiles(buckets, count)

    def snapshot_raw(self) -> Tuple[int, float, List[int]]:
        """(count, sum, per-bucket counts) under one lock acquisition —
        the structured form the OpenMetrics renderer needs to emit
        cumulative ``_bucket`` series."""
        with self._lock:
            return self.count, self.total, list(self.buckets)


def bucket_percentiles(
    buckets: List[int], count: int,
    qs: Tuple[float, ...] = (0.5, 0.95, 0.99),
) -> Dict[str, float]:
    """{"p50": v, ...} from log2 bucket counts.  Each estimate is the
    UPPER bound (2^k) of the bucket containing the quantile rank — a
    deterministic, allocation-free derivation whose error is bounded by
    the bucket width (one octave), the Monarch/Prometheus fixed-bucket
    tradeoff.  Empty histograms report 0."""
    out: Dict[str, float] = {}
    for q in qs:
        label = f"p{int(round(q * 100))}"
        if count <= 0:
            out[label] = 0.0
            continue
        rank = q * count
        cum = 0
        value = float(1 << (len(buckets) - 1))
        for k, n in enumerate(buckets):
            cum += n
            if cum >= rank:
                value = float(1 << k)
                break
        out[label] = value
    return out


class MetricsRegistry:
    def __init__(self):
        self._lock = named_lock("metrics.MetricsRegistry._lock")
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name)
            return h

    def snapshot(self) -> List[Tuple[str, float]]:
        """(name, value) rows — the system_metrics table's content.
        Histograms flatten to .count/.sum/.bucket_le_N rows."""
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            histograms = list(self._histograms.values())
        rows = [(c.name, c.value) for c in counters]
        rows += [(g.name, g.value) for g in gauges]
        for h in histograms:
            rows += h.rows()
        return sorted(rows)

    def export(self) -> Dict[str, Dict]:
        """Typed snapshot keeping the instrument kinds apart — the
        OpenMetrics exposition (obs/openmetrics.py) needs to know
        counter from gauge from histogram, which the flat ``snapshot``
        rows erase."""
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            histograms = list(self._histograms.values())
        out: Dict[str, Dict] = {
            "counters": {c.name: c.value for c in counters},
            "gauges": {g.name: g.value for g in gauges},
            "histograms": {},
        }
        for h in histograms:
            count, total, buckets = h.snapshot_raw()
            out["histograms"][h.name] = {
                "count": count, "sum": total, "buckets": buckets}
        return out

    def reset(self) -> None:
        """Tests only: drop every instrument (pre-registered names are
        re-created by re-importing callers on demand)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
        _preregister(self)


#: the process-wide registry (the default every instrumentation point
#: and the system_metrics table use)
METRICS = MetricsRegistry()


def _preregister(reg: MetricsRegistry) -> None:
    """The documented catalog (docs/observability.md) — registered at
    import so the system_metrics table is complete on a fresh process."""
    for name in (
        # query lifecycle
        "query.started", "query.finished", "query.failed",
        "query.planning_seconds_total", "query.execution_seconds_total",
        # XLA program registry / compilation
        "xla.programs_compiled", "xla.compile_seconds_total",
        "xla.registry_hits", "xla.registry_misses",
        # device <-> host transfers (the TPU tax EXPLAIN can't see)
        "device.get_calls", "device.get_bytes",
        # pages of chains that ran compacted in front of a probe, and
        # of chains that had to run again whole (exec/local)
        "chain.compact_pages", "chain.compact_fallback_pages",
        # spill + exchange volume
        "spill.bytes", "exchange.pages_serialized",
        "exchange.bytes_serialized", "exchange.pages_deserialized",
        "exchange.bytes_deserialized",
        # streaming page exchange (parallel/streams.py): pages/bytes
        # through stage-boundary streams, producer time blocked on the
        # byte cap (backpressure), mid-stream producer-death replays
        # (resume from the consumer's last acked token), and kill-path
        # aborts (pool.kill_query -> streams.abort_query)
        "exchange.stream_pages_total", "exchange.stream_bytes_total",
        "exchange.producer_stall_seconds_total",
        "exchange.stream_replays_total", "exchange.streams_aborted",
        # distributed tiers (VERDICT weak #8: fallbacks countable)
        "dist.stages_total", "dist.fallbacks",
        "multihost.stages_total", "multihost.fallbacks",
        # two-stage window shuffle lost a worker mid-flight and
        # degraded to gather + coordinator window (stage-1 re-scanned)
        "multihost.window_shuffle_degraded",
        # worker task protocol (aborted = client cancellation, not a
        # failure — alerting keys on tasks.failed alone)
        "tasks.started", "tasks.finished", "tasks.failed",
        "tasks.aborted",
        # morsel-driven split scheduler (exec/tasks.py): dispatched
        # split count, consumer stall time waiting on in-flight splits,
        # and prefetch pipeline hit/miss (a hit = the next result was
        # already buffered when the consumer asked)
        "task.splits_dispatched", "task.scheduler_stall_seconds_total",
        "task.prefetch_hits", "task.prefetch_misses",
        # memory plane: cluster low-memory killer victims
        "memory.query_killed",
        # fault-tolerance plane (parallel/failure.py + net.py +
        # testing_faults.py; docs/fault-tolerance.md).  Classified
        # transport errors by reason — one counter per reason keeps the
        # label space fixed (no per-URI series):
        "net.errors_refused", "net.errors_timeout", "net.errors_http",
        "net.errors_protocol", "net.errors_other",
        # per-site poll errors (the classified replacements for the
        # old blind `except: pass` swallows)
        "worker.ping_errors", "cluster.metrics_poll_errors",
        "cluster.memory_poll_errors",
        # retry plane: transient HTTP retries, fragment re-dispatches
        # onto survivors, and splits recovered by coordinator-local
        # execution after every worker failed
        "retry.http_total", "retry.fragments_total",
        "retry.splits_recovered_local",
        # failure-detector state machine: transitions by target state
        "worker.state_transitions", "worker.transitions_to_suspect",
        "worker.transitions_to_dead", "worker.transitions_to_recovered",
        "worker.transitions_to_alive",
        # query deadlines: coordinator kills for EXCEEDED_TIME_LIMIT
        "query.killed_deadline",
        # deterministic fault-injection harness firings
        "fault.injections_total",
        # serving tier: admission plane (serving/admission.py) — queue
        # entries/exits, rejections by reason, and time spent blocked
        # on memory headroom (distinct from concurrency queueing)
        "admission.queued_total", "admission.admitted_total",
        "admission.rejected_queue_full", "admission.rejected_timeout",
        "admission.memory_blocked_total",
        "admission.memory_stall_seconds_total",
        # serving tier: structural result cache (final rows of
        # read-only queries, keyed by plan signature, invalidated by
        # table versions) and the subplan (stage-intermediate) cache
        # at exchange boundaries (serving/cache.py)
        "cache.result_hits", "cache.result_misses",
        "cache.result_stores", "cache.result_evictions",
        "cache.result_invalidations", "cache.result_oversize",
        "cache.subplan_hits", "cache.subplan_misses",
        "cache.subplan_stores", "cache.subplan_evictions",
        "cache.subplan_invalidations", "cache.subplan_oversize",
        # iterative optimizer: successful rule applications and
        # rewrites rejected by the soundness gate
        # (planner/iterative.py + analysis/soundness.py)
        "optimizer.rule_applications", "optimizer.rule_violations",
        # kernel-soundness analyzer: value hazards (overflow +
        # lossy-cast + division) and null-policy violations found per
        # analyzed plan (analysis/kernel_soundness.py)
        "kernel.overflow_hazards", "kernel.null_violations",
        "kernel.sanitizer_escapes",
    ):
        reg.counter(name)
    for name in (
        # HBM pool accounting (memory.wire_pool_gauges attaches the
        # sampling callbacks to the active MemoryPool)
        "memory.pool_reserved_bytes", "memory.pool_peak_bytes",
        "memory.pool_limit_bytes", "memory.pool_queries",
        # live split-scheduler state (exec/tasks.py wires the
        # sampling callbacks at import)
        "task.splits_queued", "task.splits_running",
        # failure-detector worker-state census (parallel/failure.py
        # wires the sampling callbacks when a detector is live)
        "worker.state_alive", "worker.state_suspect",
        "worker.state_dead", "worker.state_recovered",
        # streaming-exchange occupancy (parallel/streams.py wires the
        # sampling callbacks at import): unacked bytes buffered across
        # live streams and streams not yet drained/aborted
        "exchange.buffered_bytes", "exchange.open_streams",
        # concurrency sanitizer (presto_tpu/sync.py, opt-in via
        # PRESTO_TPU_LOCK_SANITIZER): instrumented-lock totals sampled
        # from the process-wide LockWatcher — zero when the sanitizer
        # is off.  lock_inversions > 0 in any run is a release blocker
        # (an observed lock-order cycle arc).
        "sanitizer.lock_acquisitions", "sanitizer.lock_wait_seconds",
        "sanitizer.lock_hold_seconds", "sanitizer.lock_inversions",
        "sanitizer.locks_tracked", "sanitizer.edges_observed",
        # serving tier: live admission queue depth / admitted-and-held
        # tickets (serving/admission.py wires the sampling callbacks)
        # and cache occupancy (serving/cache.py publishes on mutation)
        "admission.queue_depth", "admission.running",
        "cache.result_bytes", "cache.result_entries",
        "cache.subplan_bytes", "cache.subplan_entries",
    ):
        reg.gauge(name)
    for name in ("query.execution_ms", "xla.compile_ms",
                 # admission queue-wait distribution (serving tier)
                 "admission.queue_wait_ms"):
        reg.histogram(name)


_preregister(METRICS)


# ---------------------------------------------------------------------------
# task registry: the system_runtime_tasks table's source
# ---------------------------------------------------------------------------


class TaskEntry:
    __slots__ = ("task_id", "source", "state", "trace_token", "_t0",
                 "elapsed_ms", "rows", "error", "splits", "concurrency",
                 "stall_ms", "prefetch_hits")

    def __init__(self, task_id: str, source: str,
                 trace_token: Optional[str] = None):
        self.task_id = task_id
        self.source = source  # "local" | "worker"
        self.state = "RUNNING"
        self.trace_token = trace_token
        self._t0 = time.perf_counter()
        self.elapsed_ms: Optional[float] = None
        self.rows: Optional[int] = None
        self.error: Optional[str] = None
        # split-scheduler footprint (exec/tasks.py; NULL until the
        # executor reports — e.g. worker shuffle-pull tasks never do)
        self.splits: Optional[int] = None
        self.concurrency: Optional[int] = None
        self.stall_ms: Optional[float] = None
        self.prefetch_hits: Optional[int] = None


class TaskRegistry:
    """Bounded live+finished view of execution tasks on this node —
    coordinator-local query executions (one degenerate task per query)
    and worker task-protocol fragments (SqlTaskManager's task list
    analog, what the reference surfaces as system.runtime.tasks)."""

    def __init__(self, limit: int = 1000):
        self._lock = named_lock("metrics.TaskRegistry._lock")
        self._entries: "Dict[str, TaskEntry]" = {}
        self._order: List[str] = []
        self.limit = limit

    def start(self, task_id: str, source: str,
              trace_token: Optional[str] = None) -> TaskEntry:
        e = TaskEntry(task_id, source, trace_token)
        with self._lock:
            if task_id not in self._entries:
                self._order.append(task_id)
            self._entries[task_id] = e
            while len(self._order) > self.limit:
                self._entries.pop(self._order.pop(0), None)
        METRICS.counter("tasks.started").inc()
        return e

    def finish(self, task_id: str, state: str = "FINISHED",
               rows: Optional[int] = None,
               error: Optional[str] = None) -> None:
        with self._lock:
            e = self._entries.get(task_id)
            if e is None:
                return
            e.state = state
            e.elapsed_ms = round((time.perf_counter() - e._t0) * 1e3, 3)
            e.rows = rows
            e.error = error
        counter = {"FINISHED": "tasks.finished",
                   "ABORTED": "tasks.aborted"}.get(state, "tasks.failed")
        METRICS.counter(counter).inc()

    def update_scheduler(self, task_id: str, splits: int, concurrency: int,
                         stall_ms: float, prefetch_hits: int) -> None:
        """Attach the split-scheduler footprint of a finished (or
        running) execution to its task row — the system_runtime_tasks
        surface of the morsel scheduler."""
        with self._lock:
            e = self._entries.get(task_id)
            if e is None:
                return
            e.splits = int(splits)
            e.concurrency = int(concurrency)
            e.stall_ms = round(float(stall_ms), 3)
            e.prefetch_hits = int(prefetch_hits)

    def entries(self) -> List[TaskEntry]:
        with self._lock:
            return [self._entries[t] for t in self._order]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._order.clear()


#: process-wide task view (system_runtime_tasks reads it)
TASKS = TaskRegistry()
