"""Trace/metrics exports: Chrome-trace JSON and the JSONL query log.

- ``chrome_trace(tracer)`` renders a tracer's spans in the Chrome
  trace-event format (the ``chrome://tracing`` / Perfetto JSON spec:
  complete "X" events with microsecond ts/dur, pid/tid lanes, plus
  "M" metadata naming the process after the query id) so a TPU query's
  life is inspectable in the standard tooling.
- ``maybe_write_trace`` drops one ``<query_id>.trace.json`` per query
  under the trace directory (``PRESTO_TPU_TRACE_DIR`` env >
  ``query.trace-dir`` config, resolved once at import with a
  ``set_trace_dir`` override hook).
- :class:`QueryLogListener` is an EventListener writing one JSON line
  per completed query — the warehouse query-log sink the reference
  builds on the EventListener SPI.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, Optional

from presto_tpu.sync import named_lock

from presto_tpu.events import (
    EventListener, MemoryKillEvent, QueryAdmittedEvent, QueryCompletedEvent,
    QueryKilledEvent, QueryQueuedEvent, WorkerStateChangeEvent,
)
from presto_tpu.obs.trace import Tracer

def _normalize_dir(path: Optional[str]) -> Optional[str]:
    """Shared disable convention with the sibling config key
    (query_log_path): empty / ``0`` / ``false`` means disabled, not a
    directory literally named ``0``."""
    if path is None or path.strip() in ("", "0", "false"):
        return None
    return path


# resolved ONCE at import (module scope: the engine-lint env-read rule's
# sanctioned place); set_trace_dir overrides for config wiring and tests
_TRACE_DIR: Optional[str] = _normalize_dir(
    os.environ.get("PRESTO_TPU_TRACE_DIR"))


def trace_dir() -> Optional[str]:
    return _TRACE_DIR


def set_trace_dir(path: Optional[str]) -> None:
    global _TRACE_DIR
    _TRACE_DIR = _normalize_dir(path)


def maybe_enable_trace_dir(config) -> Optional[str]:
    """Wire ``query.trace-dir`` from an EngineConfig; the environment
    (resolved at import) wins over config, matching the persistent
    program cache's precedence."""
    if _TRACE_DIR is not None:
        return _TRACE_DIR
    d = _normalize_dir(config.str("query.trace-dir"))
    if d:
        set_trace_dir(d)
    return d


def chrome_trace(tracer: Tracer) -> Dict[str, Any]:
    """Chrome trace-event JSON for one query's tracer.  Timestamps are
    microseconds relative to the tracer's start (perf_counter deltas —
    monotonic, so spans nest exactly as measured)."""
    pid = os.getpid()
    events = [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
         "args": {"name": f"presto_tpu query {tracer.query_id}"}},
    ]
    with tracer._lock:
        spans = list(tracer.spans)
    # base on the earliest span, not tracer construction: retroactive
    # spans (the parse that ran before tracing was decided) start
    # earlier, and Chrome rejects negative timestamps
    t_base = min([tracer.t_start] + [s.t0 for s in spans])
    tids = set()
    for s in spans:
        ev = {
            "ph": "X",
            "name": s.name,
            "cat": s.cat,
            "ts": round((s.t0 - t_base) * 1e6, 1),
            "dur": round(s.dur * 1e6, 1),
            "pid": pid,
            "tid": s.tid,
        }
        if s.args:
            ev["args"] = {k: v for k, v in s.args.items()}
        events.append(ev)
        tids.add(s.tid)
    for tid in sorted(tids):
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": f"thread-{tid}"}})
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "query_id": tracer.query_id,
            "trace_token": tracer.trace_token,
            "create_time": tracer.create_time,
            # spans past the tracer's retention cap were counted, not
            # kept — a nonzero value means the trace is a prefix
            "dropped_spans": tracer.dropped,
        },
    }


def write_trace(tracer: Tracer, directory: str) -> str:
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{tracer.query_id}.trace.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(chrome_trace(tracer), f)
        f.write("\n")
    os.replace(tmp, path)
    return path


def maybe_write_trace(tracer: Tracer) -> Optional[str]:
    d = trace_dir()
    if d is None:
        return None
    try:
        return write_trace(tracer, d)
    except OSError:
        return None  # tracing must never fail the query


class QueryLogListener(EventListener):
    """JSONL query log: one line per completed query, carrying the
    lifecycle stage times and (when the query traced) the span-tree
    rollup.  Appends are serialized and flushed per event so the log
    survives a crash with every completed query it saw."""

    def __init__(self, path: str):
        self.path = path
        self._lock = named_lock("export.QueryLogListener._lock")

    def query_completed(self, e: QueryCompletedEvent) -> None:
        from presto_tpu.obs.trace import lookup

        rec: Dict[str, Any] = {
            "query_id": e.query_id,
            "state": e.state,
            "user": e.user,
            "rows": e.rows,
            "create_time": e.create_time,
            "end_time": e.end_time,
            "wall_s": round(e.end_time - e.create_time, 6),
            "sql": e.sql,
        }
        for k in ("error", "trace_token", "dist_stages", "dist_fallback",
                  "planning_ms", "compile_ms", "execution_ms",
                  "cache_hit", "queued_ms", "memory_blocked_ms",
                  "findings", "worst_estimate_ratio"):
            v = getattr(e, k, None)
            if v is not None:
                rec[k] = v
        tracer = lookup(e.query_id)
        if tracer is not None:
            rec["spans"] = tracer.summary()
        self._append(rec)

    def memory_killed(self, e: MemoryKillEvent) -> None:
        """One ``"event": "memory_kill"`` line per low-memory-killer
        victim — the kill DECISION, distinct from (and preceding) the
        victim's completion line."""
        self._append({
            "event": "memory_kill",
            "query_id": e.query_id,
            "freed_bytes": e.freed_bytes,
            "reserved_bytes": e.reserved_bytes,
            "limit_bytes": e.limit_bytes,
            "kill_time": e.kill_time,
        })

    def query_killed(self, e: QueryKilledEvent) -> None:
        """One ``"event": "query_killed"`` line per coordinator kill
        decision (deadline / policy) with its reason code — e.g.
        ``EXCEEDED_TIME_LIMIT`` when ``query.max-execution-time``
        expired (docs/fault-tolerance.md)."""
        self._append({
            "event": "query_killed",
            "query_id": e.query_id,
            "reason": e.reason,
            "message": e.message,
            "limit_s": e.limit_s,
            "elapsed_s": e.elapsed_s,
            "kill_time": e.kill_time,
        })

    def query_queued(self, e: QueryQueuedEvent) -> None:
        """One ``"event": "query_queued"`` line per admission-queue
        entry (serving tier): group + live position at enqueue time."""
        self._append({
            "event": "query_queued",
            "query_id": e.query_id,
            "user": e.user,
            "group": e.group,
            "position": e.position,
            "queue_time": e.queue_time,
        })

    def query_admitted(self, e: QueryAdmittedEvent) -> None:
        """One ``"event": "query_admitted"`` line per dispatch: queue
        wait and the memory projection the admission was made under."""
        self._append({
            "event": "query_admitted",
            "query_id": e.query_id,
            "group": e.group,
            "queued_ms": e.queued_ms,
            "projected_bytes": e.projected_bytes,
            "admit_time": e.admit_time,
        })

    def worker_state_changed(self, e: WorkerStateChangeEvent) -> None:
        """One ``"event": "worker_state_change"`` line per failure-
        detector transition — the audit trail that a mid-query retry
        actually crossed a worker death, not just a slow response."""
        self._append({
            "event": "worker_state_change",
            "uri": e.uri,
            "old_state": e.old_state,
            "new_state": e.new_state,
            "reason": e.reason,
            "change_time": e.change_time,
        })

    def _append(self, rec: Dict[str, Any]) -> None:
        line = json.dumps(rec, default=str)
        try:
            with self._lock:
                with open(self.path, "a") as f:
                    f.write(line + "\n")
        except OSError:
            pass  # a full disk must never fail an already-run query
