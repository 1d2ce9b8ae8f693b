"""Telemetry history: a bounded metrics ring + per-query resource
timelines.

Two retention planes, both bounded (a serving process must never grow
telemetry without limit):

1. :class:`MetricsHistory` — a process-wide ring of catalog samples.
   A named daemon thread wakes every ``PRESTO_TPU_METRICS_HISTORY_MS``
   (0 = off; the servers arm a 1s default when unset) and records one
   *tick*: every gauge's value, every counter's per-second rate since
   the previous tick, and every histogram's observation rate plus its
   current p50/p95/p99 (derived from the log2 buckets).  The ring keeps
   the last ``PRESTO_TPU_METRICS_HISTORY_TICKS`` ticks — retention is
   ``ticks x cadence`` (~8.5 min at defaults).  Exposed as the
   ``system_metrics_history`` table and ``GET /v1/metrics/history``.

   Prometheus-vs-history tradeoff: a scraper owns long-term storage;
   the ring exists so a cluster WITHOUT external scraping can still
   answer "what did queue depth / buffered bytes look like over the
   last few minutes" — the autoscale + doctor input — from the process
   itself.  Because names come from the live registry, the engine-lint
   metric-catalog rule covers everything the ring samples by
   construction; derived suffixes (``.rate``, ``.p50``...) are
   computed, never free-hand literals.

2. :class:`QueryTimeline` — one bounded per-query buffer of
   ``(ts_ms, metric, value)`` points appended by the runner/exec/
   parallel hot paths (memory reservation, exchange buffered bytes,
   splits done per stage, admission queue depth),
   plus an ``annotations`` dict of per-query scalars the doctor
   consumes (queued/memory-blocked ms, spill bytes, producer stall,
   per-partition row counts, per-worker fragment durations, findings).
   Registry + thread-local activation mirror obs/progress.py exactly;
   the disabled fast path is ONE thread-local read returning ``None``
   (:func:`record_point` costs a getattr and a branch when no timeline
   is active — the "no measurable overhead when disabled" contract).

Like the rest of ``obs``, this module sits below every execution layer
and imports none of them.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

from presto_tpu.envflag import EnvFlag, EnvInt
from presto_tpu.sync import named_lock

#: sampler cadence in ms; 0 disables.  Servers pass ``default_ms=1000``
#: to ``HISTORY.start`` so history is on in serving processes unless
#: the environment explicitly set 0.
metrics_history_ms = EnvInt("PRESTO_TPU_METRICS_HISTORY_MS", 0, floor=0)
#: ring length in ticks (bounds retained memory: ticks x rows/tick)
metrics_history_ticks = EnvInt(
    "PRESTO_TPU_METRICS_HISTORY_TICKS", 512, floor=8)
#: per-query timeline point cap (deque maxlen; oldest points evict)
timeline_points_max = EnvInt("PRESTO_TPU_TIMELINE_POINTS", 2048, floor=64)
#: master switch for per-query timelines — when off, ``ensure_timeline``
#: returns None, nothing registers, and every hot-path hook falls
#: through its single None check
timelines_enabled = EnvFlag("PRESTO_TPU_QUERY_TIMELINES", True)


# ---------------------------------------------------------------------------
# process-wide metrics history ring
# ---------------------------------------------------------------------------


class MetricsHistory:
    """Bounded ring of metrics-catalog samples (see module doc)."""

    def __init__(self, registry=None, max_ticks: Optional[int] = None):
        self._registry = registry
        self._lock = named_lock("timeseries.MetricsHistory._lock")
        self._ticks: "collections.deque" = collections.deque(
            maxlen=max_ticks or metrics_history_ticks())
        # (perf_counter, counter values, histogram counts) of the last
        # tick — rates are deltas against it (perf_counter based:
        # durations never mix with wall-clock)
        self._prev: Optional[Tuple[float, Dict[str, float],
                                   Dict[str, int]]] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.interval_ms = 0

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from presto_tpu.obs.metrics import METRICS

        return METRICS

    # -- sampling -------------------------------------------------------
    def sample_once(self) -> int:
        """Record one tick; returns the number of rows sampled."""
        from presto_tpu.obs.metrics import bucket_percentiles

        ex = self._reg().export()
        now_pc = time.perf_counter()
        ts_ms = time.time() * 1e3  # epoch stamp (standalone, no deltas)
        rows: List[Tuple[str, float]] = []
        for name, value in ex["gauges"].items():
            v = float(value)
            if v == v:  # an unwired gauge's NaN must not enter the ring
                rows.append((name, v))
        counters = {n: float(v) for n, v in ex["counters"].items()}
        hist_counts = {n: int(h["count"])
                       for n, h in ex["histograms"].items()}
        for name, h in ex["histograms"].items():
            if h["count"]:
                for pname, pv in bucket_percentiles(
                        h["buckets"], h["count"]).items():
                    rows.append((f"{name}.{pname}", pv))
        # prev + ticks under one lock: sample_once may be driven from
        # both the sampler thread and callers (tests, a manual tick)
        with self._lock:
            prev = self._prev
            if prev is not None:
                t_prev, prev_counters, prev_hists = prev
                dt = max(now_pc - t_prev, 1e-9)
                for name, v in counters.items():
                    rows.append(
                        (name + ".rate",
                         max(0.0, v - prev_counters.get(name, 0.0)) / dt))
                for name, c in hist_counts.items():
                    rows.append(
                        (name + ".count.rate",
                         max(0, c - prev_hists.get(name, 0)) / dt))
            self._prev = (now_pc, counters, hist_counts)
            self._ticks.append((ts_ms, rows))
        return len(rows)

    # -- sampler lifecycle ---------------------------------------------
    def start(self, interval_ms: Optional[int] = None,
              default_ms: int = 0) -> bool:
        """Arm the sampler.  Explicit ``interval_ms`` wins; otherwise
        the env knob; otherwise ``default_ms`` (servers pass 1000).
        Returns whether a sampler is running after the call."""
        ms = interval_ms if interval_ms is not None \
            else (metrics_history_ms() or default_ms)
        with self._lock:
            if self._thread is not None:
                return True
            if ms <= 0:
                return False
            self.interval_ms = int(ms)
            self._stop = threading.Event()
            stop = self._stop
            t = threading.Thread(
                target=self._run, args=(stop, ms / 1e3),
                name="obs-history-sampler", daemon=True)
            self._thread = t
        t.start()
        return True

    def _run(self, stop: threading.Event, interval_s: float) -> None:
        while True:
            try:
                self.sample_once()
            except Exception:
                # a mid-shutdown registry hiccup must not kill the
                # sampler; the next tick retries
                pass  # noqa: S110 - sampling is best-effort
            if stop.wait(interval_s):
                return

    def stop(self) -> None:
        with self._lock:
            t = self._thread
            self._thread = None
            stop = self._stop
        stop.set()
        if t is not None:
            t.join(timeout=5.0)

    @property
    def running(self) -> bool:
        with self._lock:
            return self._thread is not None

    # -- readers --------------------------------------------------------
    def rows(self) -> List[Tuple[float, str, float]]:
        """Flattened (ts_ms, name, value) rows, oldest tick first —
        the ``system_metrics_history`` table and the history endpoint
        read exactly this shape."""
        with self._lock:
            ticks = list(self._ticks)
        out: List[Tuple[float, str, float]] = []
        for ts_ms, rows in ticks:
            out.extend((ts_ms, name, value) for name, value in rows)
        return out

    def tick_count(self) -> int:
        with self._lock:
            return len(self._ticks)

    def clear(self) -> None:
        with self._lock:
            self._ticks.clear()
            self._prev = None


#: the process-wide history ring (servers arm its sampler; the
#: system_metrics_history table reads it)
HISTORY = MetricsHistory()


# ---------------------------------------------------------------------------
# per-query resource timelines
# ---------------------------------------------------------------------------


class QueryTimeline:
    """One query's bounded (ts_ms, metric, value) buffer + the
    annotation dict shared by admission, exec and the doctor.
    Timestamps are ms since the timeline's creation (perf_counter
    deltas — durations, never wall-clock)."""

    __slots__ = ("query_id", "t0", "dropped", "max_points", "_points",
                 "_ann", "_lock")

    def __init__(self, query_id: str, max_points: Optional[int] = None):
        self.query_id = query_id
        self.t0 = time.perf_counter()
        self.max_points = max_points or timeline_points_max()
        self.dropped = 0
        self._points: "collections.deque" = collections.deque(
            maxlen=self.max_points)
        self._ann: Dict[str, object] = {}
        self._lock = named_lock("timeseries.QueryTimeline._lock")

    # -- writers --------------------------------------------------------
    def record(self, name: str, value: float) -> None:
        ts_ms = (time.perf_counter() - self.t0) * 1e3
        with self._lock:
            if len(self._points) == self.max_points:
                self.dropped += 1  # the deque evicts the oldest point
            self._points.append((ts_ms, name, float(value)))

    def annotate(self, key: str, value) -> None:
        with self._lock:
            self._ann[key] = value

    def bump(self, key: str, delta: float) -> float:
        """Additive annotation (stall seconds, spill bytes...)."""
        with self._lock:
            v = float(self._ann.get(key, 0.0)) + float(delta)
            self._ann[key] = v
            return v

    def extend(self, key: str, subkey: str, value) -> None:
        """Append ``value`` to ``annotations[key][subkey]`` (per-stage
        partition counts, per-worker fragment durations...)."""
        with self._lock:
            series = self._ann.setdefault(key, {})
            series.setdefault(subkey, []).append(value)

    # -- readers --------------------------------------------------------
    def annotation(self, key: str, default=None):
        with self._lock:
            return self._ann.get(key, default)

    def annotations(self) -> Dict[str, object]:
        with self._lock:
            return dict(self._ann)

    def points(self) -> List[Tuple[float, str, float]]:
        with self._lock:
            return list(self._points)

    def snapshot(self) -> Dict:
        with self._lock:
            pts = [[round(ts, 3), name, value]
                   for ts, name, value in self._points]
            ann = dict(self._ann)
            dropped = self.dropped
        return {
            "queryId": self.query_id,
            "points": pts,
            "dropped": dropped,
            "annotations": ann,
        }


# ---------------------------------------------------------------------------
# process registry + thread-local activation (mirrors obs/progress.py)
# ---------------------------------------------------------------------------

_REGISTRY_MAX = 256
_REGISTRY: "collections.OrderedDict[str, QueryTimeline]" = (
    collections.OrderedDict())
_REGISTRY_LOCK = named_lock("timeseries._REGISTRY_LOCK")

_ACTIVE = threading.local()


def register_timeline(timeline: QueryTimeline) -> QueryTimeline:
    with _REGISTRY_LOCK:
        _REGISTRY[timeline.query_id] = timeline
        _REGISTRY.move_to_end(timeline.query_id)
        while len(_REGISTRY) > _REGISTRY_MAX:
            _REGISTRY.popitem(last=False)
    return timeline


def ensure_timeline(query_id: Optional[str]) -> Optional[QueryTimeline]:
    """Get-or-create the timeline for ``query_id`` (admission runs
    before the runner registers one, so both share this entry point).
    Returns ``None`` when timelines are disabled or the id is empty."""
    if not query_id or not timelines_enabled():
        return None
    with _REGISTRY_LOCK:
        tl = _REGISTRY.get(query_id)
        if tl is not None:
            _REGISTRY.move_to_end(query_id)
            return tl
    return register_timeline(QueryTimeline(query_id))


def timeline_for(query_id: str) -> Optional[QueryTimeline]:
    with _REGISTRY_LOCK:
        return _REGISTRY.get(query_id)


def current_timeline() -> Optional[QueryTimeline]:
    return getattr(_ACTIVE, "timeline", None)


def record_point(name: str, value: float) -> None:
    """Hot-path append: one thread-local read; a no-op (no allocation,
    no clock read) when no timeline is active."""
    tl = getattr(_ACTIVE, "timeline", None)
    if tl is not None:
        tl.record(name, value)


class _Activation:
    __slots__ = ("_timeline", "_prev")

    def __init__(self, timeline: Optional[QueryTimeline]):
        self._timeline = timeline

    def __enter__(self):
        self._prev = getattr(_ACTIVE, "timeline", None)
        if self._timeline is not None:
            _ACTIVE.timeline = self._timeline
        return self._timeline

    def __exit__(self, *exc):
        if self._timeline is not None:
            _ACTIVE.timeline = self._prev
        return False


def recording(timeline: Optional[QueryTimeline]) -> _Activation:
    """Bind a timeline to the current thread (``None`` = no-op),
    exactly like ``obs.tracing`` / ``obs.publishing``."""
    return _Activation(timeline)
