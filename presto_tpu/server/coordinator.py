"""Coordinator REST server.

Reference analog: the V1 statement protocol —
``server/protocol/StatementResource.java:82`` (POST /v1/statement
creates a query; results are paged via GET nextUri with token
acknowledgement; DELETE cancels) plus the info/status resources
(``server/ServerInfoResource``, ``QueryResource``).  stdlib
http.server stands in for airlift/jetty; query execution runs on a
worker thread per query with paged result buffers.

Protocol (JSON):
  POST /v1/statement            body = SQL
  GET  /v1/statement/{id}/{tok} next page
  DELETE /v1/statement/{id}     cancel
  GET  /v1/info                 server info
  GET  /v1/query                finished/running query summaries
Responses carry: id, columns [{name, type}], data [[row...]...],
stats {state, rows}, error?, nextUri?.
"""

from __future__ import annotations

import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from presto_tpu import __version__
from presto_tpu.runner import QueryRunner
from presto_tpu.sync import named_lock

PAGE_ROWS = 1000

# Minimal cluster console (the reference serves a React app from
# presto-main/src/main/resources/webapp/; this single inline page covers
# the same first-stop view — cluster tiles + live query list + a
# per-query detail view (stage progress table + span timeline) — from
# the same REST resources).
_UI_HTML = """<!doctype html>
<html><head><meta charset="utf-8"><title>presto-tpu</title>
<style>
 body{font-family:system-ui,sans-serif;margin:2rem;background:#16181d;color:#e8e8e8}
 h1{font-size:1.3rem} h2{font-size:1.05rem;color:#9aa0ab}
 .tiles{display:flex;gap:1rem;margin:1rem 0}
 .tile{background:#23262e;border-radius:8px;padding:1rem 1.5rem;min-width:8rem}
 .tile .v{font-size:1.8rem;font-weight:600} .tile .l{color:#9aa0ab;font-size:.8rem}
 table{border-collapse:collapse;width:100%;margin-top:1rem}
 th,td{text-align:left;padding:.4rem .6rem;border-bottom:1px solid #2e323b;font-size:.85rem}
 th{color:#9aa0ab;font-weight:500}
 .FINISHED{color:#6fcf97}.RUNNING{color:#56ccf2}.FAILED,.CANCELED{color:#eb5757}
 .QUEUED{color:#f2c94c} td.q{font-family:ui-monospace,monospace;max-width:40rem;
 overflow:hidden;text-overflow:ellipsis;white-space:nowrap}
 tr.row{cursor:pointer} tr.row:hover{background:#1c1f26}
 #detail{display:none;background:#23262e;border-radius:8px;padding:1rem 1.5rem;margin:1rem 0}
 .lane{position:relative;height:18px;margin:2px 0;background:#1a1d23}
 .sp{position:absolute;height:14px;top:2px;background:#56ccf2;border-radius:2px;
  overflow:hidden;font-size:.65rem;color:#0b0d10;white-space:nowrap;padding:0 2px}
 .sp.lifecycle{background:#6fcf97}.sp.compile{background:#f2c94c}
 .sp.exchange{background:#bb6bd9}.sp.device{background:#eb5757}
 .bar{background:#1a1d23;border-radius:4px;height:8px;margin-top:2px}
 .bar>div{background:#56ccf2;border-radius:4px;height:8px}
</style></head><body>
<h1>presto-tpu cluster console</h1>
<div class="tiles" id="tiles"></div>
<div id="workers"></div>
<div id="detail"></div>
<table><thead><tr><th>query id</th><th>state</th><th>progress</th><th>rows</th><th>sql</th></tr></thead>
<tbody id="queries"></tbody></table>
<script>
let selected = null;
async function refresh(){
  const c = await (await fetch('/v1/cluster')).json();
  document.getElementById('tiles').innerHTML =
    ['runningQueries','queuedQueries','finishedQueries','failedQueries']
    .map(k=>`<div class="tile"><div class="v">${c[k]??0}</div><div class="l">${k.replace('Queries',' queries')}</div></div>`).join('')
    + (c.totalBytes?`<div class="tile"><div class="v">${(100*c.reservedBytes/c.totalBytes).toFixed(1)}%</div><div class="l">pool reserved</div></div>`:'');
  const ws = await (await fetch('/v1/worker')).json();
  document.getElementById('workers').innerHTML = !ws.length ? '' :
    '<h2>workers</h2><table><thead><tr><th>worker</th><th>detector state</th>'+
    '<th>consecutive failures</th><th>last heartbeat</th></tr></thead><tbody>'+
    ws.map(w=>{
      const cls = {ALIVE:'FINISHED',RECOVERED:'FINISHED',SUSPECT:'QUEUED',
                   DEAD:'FAILED'}[w.state]||'';
      const hb = w.last_heartbeat_ms==null?'never'
                 :(w.last_heartbeat_ms/1000).toFixed(1)+'s ago';
      return `<tr><td>${w.uri}</td><td class="${cls}">${w.state}</td>`+
             `<td>${w.consecutive_failures}</td><td>${hb}</td></tr>`;
    }).join('')+'</tbody></table>';
  const qs = await (await fetch('/v1/query')).json();
  document.getElementById('queries').innerHTML = qs.reverse().map(q=>
    `<tr class="row" onclick="select('${q.id}')"><td>${q.id}</td>`+
    `<td class="${q.state}">${q.state}</td>`+
    `<td>${q.state==='QUEUED'&&q.queuePosition!=null?'queue #'+q.queuePosition
         :q.progress==null?'':q.progress.toFixed(0)+'%'}</td>`+
    `<td>${q.rows}</td><td class="q">${q.query.replace(/</g,'&lt;')}</td></tr>`).join('');
  if (selected) detail(selected);
}
function select(id){ selected = (selected===id)?null:id; detail(selected); }
async function detail(id){
  const box = document.getElementById('detail');
  if (!id){ box.style.display='none'; return; }
  let html = `<h2>query ${id}</h2>`;
  const pr = await fetch(`/v1/query/${id}/progress`);
  if (pr.ok){
    const p = await pr.json();
    html += `<div>progress ${p.progressPercentage}% · ${p.elapsedMs}ms</div>`;
    html += '<table><thead><tr><th>stage</th><th>state</th><th>splits</th>'+
            '<th>rows</th><th>bytes</th><th></th></tr></thead><tbody>';
    for (const s of p.stages){
      const tot = s.splitsTotal, pct = tot?100*s.splitsDone/tot:0;
      html += `<tr><td>${s.stage}</td><td class="${s.state}">${s.state}</td>`+
        `<td>${s.splitsDone}/${tot??'?'}</td><td>${s.rows}</td><td>${s.bytes}</td>`+
        `<td style="min-width:8rem"><div class="bar"><div style="width:${pct.toFixed(0)}%"></div></div></td></tr>`;
    }
    html += '</tbody></table>';
  }
  const tr = await fetch(`/v1/query/${id}/trace`);
  if (tr.ok){
    // span timeline from the trace registry: top spans by duration,
    // one lane per thread, scaled to the trace extent
    const t = await tr.json();
    const evs = t.traceEvents.filter(e=>e.ph==='X');
    if (evs.length){
      const end = Math.max(...evs.map(e=>e.ts+e.dur));
      const top = evs.sort((a,b)=>b.dur-a.dur).slice(0,60);
      const tids = [...new Set(top.map(e=>e.tid))];
      html += `<h2>span timeline (${evs.length} spans, ${(end/1000).toFixed(1)}ms)</h2>`;
      for (const tid of tids){
        html += '<div class="lane">' + top.filter(e=>e.tid===tid).map(e=>
          `<div class="sp ${e.cat}" title="${e.name} ${(e.dur/1000).toFixed(2)}ms"`+
          ` style="left:${(100*e.ts/end).toFixed(2)}%;width:${Math.max(100*e.dur/end,.3).toFixed(2)}%">${e.name}</div>`
        ).join('') + '</div>';
      }
    }
  } else if (!pr.ok) {
    html += '<div>no progress or trace recorded for this query</div>';
  }
  const or_ = await fetch(`/v1/query/${id}/operators`);
  if (or_.ok){
    // per-operator est/actual rows (collect_stats sessions)
    const o = await or_.json();
    if (o.operators && o.operators.length){
      html += '<h2>operators</h2><table><thead><tr><th>operator</th>'+
              '<th>est rows</th><th>actual rows</th><th>ratio</th>'+
              '<th>pages</th><th>wall ms</th></tr></thead><tbody>';
      for (const op of o.operators){
        html += `<tr><td>${op.node}#${op.occ}</td>`+
          `<td>${op.est_rows==null?'':Number(op.est_rows).toFixed(0)}</td>`+
          `<td>${op.rows}</td>`+
          `<td>${op.ratio==null?'':'×'+Number(op.ratio).toFixed(1)}</td>`+
          `<td>${op.pages}</td><td>${op.wall_ms}</td></tr>`;
      }
      html += '</tbody></table>';
    }
  }
  const dr = await fetch(`/v1/query/${id}/doctor`);
  if (dr.ok){
    // post-query diagnosis (obs/doctor.py): ranked bottleneck findings
    const d = await dr.json();
    if (d.findings && d.findings.length){
      html += '<h2>diagnosis</h2><table><thead><tr><th>#</th><th>rule</th>'+
              '<th>score</th><th>summary</th></tr></thead><tbody>';
      d.findings.forEach((f,i)=>{
        html += `<tr><td>${i+1}</td><td>${f.rule}</td>`+
          `<td>${Number(f.score).toFixed(2)}</td>`+
          `<td class="q">${String(f.summary).replace(/</g,'&lt;')}</td></tr>`;
      });
      html += '</tbody></table>';
    }
  }
  box.innerHTML = html; box.style.display='block';
}
refresh(); setInterval(refresh, 2000);
</script></body></html>
"""


class _QueryState:
    def __init__(self, qid: str, sql: str):
        self.id = qid
        self.sql = sql
        self.state = "QUEUED"  # QUEUED -> RUNNING -> FINISHED | FAILED | CANCELED
        self.columns: List[dict] = []
        self.rows: List[tuple] = []
        self.error: Optional[str] = None
        self.done = threading.Event()
        # the computation thread: outlives `done` on cancel (DELETE
        # sets done to unblock the client; the thread runs to the end)
        self.thread: Optional[threading.Thread] = None
        # distributed-tier outcome: stage count and (when the query
        # silently ran locally) the fallback reason — surfaced in the
        # statement-protocol stats so clients see fallbacks without
        # querying system_runtime_queries
        self.dist_stages: Optional[int] = None
        self.dist_fallback: Optional[str] = None
        # lifecycle stage times from the obs span spine (NULL-safe)
        self.planning_ms: Optional[float] = None
        self.compile_ms: Optional[float] = None
        self.execution_ms: Optional[float] = None
        self.host_reads: Optional[int] = None
        self.compacted_pages: Optional[int] = None
        self.compact_fallback_pages: Optional[int] = None
        self.expand_retries: Optional[int] = None
        self.expanded_rows: Optional[int] = None
        self.arith_checked: Optional[int] = None
        self.arith_proven: Optional[int] = None
        self.chain_probes: Optional[int] = None
        # client-supplied request correlation (X-Presto-Trace-Token)
        self.trace_token: Optional[str] = None
        # deadline bookkeeping: the effective limit (None = none) and
        # the monotonic instant execution started
        self.deadline_s: Optional[float] = None
        self.t_running: Optional[float] = None
        # the admission ticket this query holds (serving/admission.py;
        # set while queued) — released once-only through the
        # controller, so a kill frees the slot immediately instead of
        # waiting for the zombie thread
        self.ticket = None
        # statement error code for policy failures (QUERY_QUEUE_FULL /
        # EXCEEDED_QUEUE_TIME / EXCEEDED_TIME_LIMIT); None for generic
        # execution errors
        self.error_code: Optional[str] = None
        # serving-tier result provenance (statement stats cacheHit)
        self.cache_hit: Optional[bool] = None
        # admission-plane waits + doctor findings (NULL-safe, copied
        # off the result like the stage times above)
        self.queued_ms: Optional[float] = None
        self.memory_blocked_ms: Optional[float] = None
        self.findings: Optional[list] = None
        # live queue position served while QUEUED (filled per response)
        self.queue_position: Optional[int] = None

    @property
    def group_released(self) -> bool:
        """Whether the admission slot has been freed (legacy surface of
        the pre-serving-tier flag; now the ticket's released state)."""
        return self.ticket is not None and self.ticket.released

    def summary(self) -> dict:
        from presto_tpu import obs

        prog = obs.progress_for(self.id)
        return {
            "id": self.id,
            "query": self.sql,
            "state": self.state,
            "rows": len(self.rows),
            "progress": (100.0 if self.state == "FINISHED"
                         else prog.percentage() if prog is not None
                         else None),
            "queuePosition": self.queue_position
            if self.state == "QUEUED" else None,
        }


class CoordinatorServer:
    """Embeds a QueryRunner behind the REST protocol.  Queries run on
    daemon threads (the coordinator's query-execution pool); the state
    machine mirrors QueryState.java:21 (trimmed to the states a
    single-process coordinator hits)."""

    def __init__(self, runner: QueryRunner, host: str = "127.0.0.1", port: int = 0,
                 resource_groups=None, worker_uris=(), memory_threshold: float = 0.95,
                 authenticator=None, max_execution_time: float = 0.0,
                 max_queued_time: float = 600.0, deadline_grace: float = 5.0,
                 detector=None, admission=None,
                 admission_memory_fraction: float = 0.9,
                 admission_reserve_bytes: int = 0):
        from presto_tpu.resource_groups import ResourceGroupManager

        # optional PasswordAuthenticator (server/security + the
        # password-authenticator plugins): HTTP Basic on /v1/statement
        self.authenticator = authenticator
        self.runner = runner
        self.queries: Dict[str, _QueryState] = {}
        self.resource_groups = resource_groups or ResourceGroupManager()
        self.worker_uris = list(worker_uris)
        # query deadlines (query.max-execution-time / max-queued-time
        # config keys): the coordinator kills a query that runs past
        # its deadline — frees its memory reservations, emits a
        # QueryKilledEvent(EXCEEDED_TIME_LIMIT), fails the statement.
        # The deadline is OPT-IN (default 0 = none: the legacy 600s
        # was a long-poll bound, not a kill); the queue bound replaces
        # the old hard-coded 600s acquire wait.
        self.max_execution_time = float(max_execution_time)
        self.max_queued_time = float(max_queued_time)
        self.deadline_grace = float(deadline_grace)
        # worker failure detector (parallel/failure.py): background
        # heartbeats with backoff, state machine per worker, surfaced
        # through /v1/worker, system_runtime_workers and the web UI;
        # transitions flow into the event pipeline (query log)
        from presto_tpu.parallel.failure import FailureDetector

        self.failure_detector = detector or FailureDetector(self.worker_uris)
        import time as _time

        from presto_tpu.events import WorkerStateChangeEvent

        self.failure_detector.add_transition_listener(
            lambda uri, old, new, reason:
            runner.events.worker_state_changed(WorkerStateChangeEvent(
                uri=uri, old_state=old, new_state=new, reason=reason,
                change_time=_time.time())))
        self._lock = named_lock("coordinator.CoordinatorServer._lock")
        # serving-tier admission plane (serving/admission.py): every
        # statement passes the memory-aware controller — resource-group
        # concurrency + projected pool headroom — instead of a bare
        # group.acquire; queue positions flow back through the async
        # statement protocol, the CLI and the web UI
        from presto_tpu.serving.admission import AdmissionController

        self.admission = admission or AdmissionController(
            self.resource_groups,
            pool=getattr(runner.executor, "memory_pool", None),
            memory_fraction=admission_memory_fraction,
            reserve_bytes=admission_reserve_bytes,
            events=runner.events)
        # cluster-wide OOM protection (memory/ClusterMemoryManager.java:88):
        # polls local + worker pools, kills the biggest reserver at the
        # threshold. Only active when the executor runs with a pool.
        self.memory_manager = None
        pool = getattr(runner.executor, "memory_pool", None)
        if pool is not None:
            from presto_tpu.cluster_memory import ClusterMemoryManager
            from presto_tpu.memory import wire_pool_gauges

            wire_pool_gauges(pool)
            self.memory_manager = ClusterMemoryManager(
                pool, self._kill_query, worker_uris=worker_uris,
                threshold=memory_threshold, events=runner.events)
        # cluster fan-in wiring: any SystemConnector already registered
        # in this runner's catalog gets the coordinator's worker polls,
        # so system_metrics grows its per-node rows + cluster rollup
        # and system_memory_pools covers the fleet without the caller
        # wiring callbacks by hand (explicitly injected ones win)
        from presto_tpu.connectors.system import SystemConnector

        for conn in runner.catalog._connectors.values():
            if isinstance(conn, SystemConnector):
                if conn.remote_metrics is None:
                    conn.remote_metrics = self.remote_metrics
                if conn.remote_history is None:
                    conn.remote_history = self.remote_history
                if conn.pools is None:
                    conn.pools = self.memory_pool_rows
                if conn.workers is None:
                    conn.workers = self.worker_rows
        # availability-transition logging for the metrics/memory polls:
        # once per state change, never per poll cycle
        from presto_tpu.net import PollHealth

        self._metrics_poll_health = PollHealth("worker metrics")
        self._memory_poll_health = PollHealth("worker memory")
        self._history_poll_health = PollHealth("worker history")
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _json(self, code: int, obj) -> None:
                # default=str: timestamps/decimals render as ISO strings
                # (the reference's JSON protocol does the same)
                body = json.dumps(obj, default=str).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _html(self, code: int, body: str) -> None:
                raw = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", "text/html; charset=utf-8")
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def _authenticated(self) -> bool:
                if outer.authenticator is None:
                    return True
                from presto_tpu.security import (
                    AuthenticationError, parse_basic_auth, parse_bearer_auth,
                )

                header = self.headers.get("Authorization", "")
                token = parse_bearer_auth(header)
                if token is not None \
                        and hasattr(outer.authenticator,
                                    "authenticate_token"):
                    try:
                        outer.authenticator.authenticate_token(token)
                        return True
                    except AuthenticationError:
                        pass
                got = parse_basic_auth(header)
                if got is not None:
                    try:
                        outer.authenticator.authenticate(*got)
                        return True
                    except AuthenticationError:
                        pass
                self.send_response(401)
                self.send_header("WWW-Authenticate", "Basic realm=\"presto\"")
                self.send_header("Content-Length", "0")
                self.end_headers()
                return False

            def do_POST(self):
                if self.path != "/v1/statement":
                    self._json(404, {"error": "not found"})
                    return
                if not self._authenticated():
                    return
                n = int(self.headers.get("Content-Length", "0"))
                sql = self.rfile.read(n).decode()
                q = outer._submit(
                    sql,
                    trace_token=self.headers.get("X-Presto-Trace-Token"))
                # X-Presto-Async: the reference protocol's real shape —
                # return immediately with state + progress; the client
                # polls nextUri until the state is terminal.  Without
                # the header the legacy blocking behavior is kept.
                if self.headers.get("X-Presto-Async"):
                    q.done.wait(timeout=0.05)  # fast queries: one page
                else:
                    # config-driven long-poll bound (was a magic 600):
                    # with a deadline set, the deadline killer fires
                    # within limit+grace, so the wait below always
                    # returns a terminal (or pollable) page — a
                    # deadline-exceeding query can never hang the POST
                    q.done.wait(timeout=outer._blocking_wait(q))
                self._json(200, outer._page_response(q, 0))

            def do_GET(self):
                parts = [p for p in self.path.split("/")
                         if p and not p.startswith("?")]
                if parts and parts[-1].split("?")[0] == "metrics" \
                        and parts[0] == "v1" and len(parts) == 2:
                    # OpenMetrics exposition (Prometheus scrape target);
                    # ?format=json serves the machine-to-machine form
                    from presto_tpu.obs import openmetrics

                    if "format=json" in self.path:
                        self._json(200, openmetrics.json_form("local"))
                    else:
                        body = openmetrics.render().encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         openmetrics.CONTENT_TYPE)
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "metrics"] \
                        and parts[2].split("?")[0] == "history":
                    # cluster metrics history: the local ring plus every
                    # worker's, keyed by node (system_metrics_history's
                    # HTTP twin)
                    self._json(200, outer.metrics_history())
                    return
                if parts == ["v1", "info"]:
                    self._json(200, {
                        "nodeVersion": {"version": __version__},
                        "coordinator": True,
                        "state": "ACTIVE",
                    })
                    return
                if parts == ["v1", "query"]:
                    with outer._lock:
                        self._json(200, [q.summary() for q in outer.queries.values()])
                    return
                if parts == ["v1", "cluster"]:
                    self._json(200, outer._cluster_stats())
                    return
                if parts == ["v1", "worker"]:
                    # failure-detector view of the worker fleet (feeds
                    # the web UI worker list; same rows as the
                    # system_runtime_workers table)
                    self._json(200, outer.worker_rows())
                    return
                if parts in ([], ["ui"]):
                    self._html(200, _UI_HTML)
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "progress":
                    # live stage table + monotone percentage for the
                    # web UI's detail view and external pollers
                    from presto_tpu import obs

                    prog = obs.progress_for(parts[2])
                    if prog is None:
                        self._json(404, {"error": "no progress for query "
                                                  f"{parts[2]}"})
                        return
                    self._json(200, prog.snapshot())
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "trace":
                    # per-query Chrome-trace JSON (open in Perfetto /
                    # chrome://tracing); works by query id or trace token
                    from presto_tpu import obs

                    tracer = obs.lookup(parts[2])
                    if tracer is None:
                        self._json(404, {"error": "no trace for query "
                                                  f"{parts[2]} (enable the "
                                                  "trace session property)"})
                        return
                    self._json(200, obs.chrome_trace(tracer))
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "timeline":
                    # per-query resource timeline (obs/timeseries.py):
                    # bounded (ts_ms, metric, value) points + the
                    # annotation dict the doctor consumes
                    from presto_tpu import obs

                    tl = obs.timeline_for(parts[2])
                    if tl is None:
                        self._json(404, {"error": "no timeline for "
                                                  f"query {parts[2]}"})
                        return
                    self._json(200, tl.snapshot())
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "operators":
                    # per-operator est/actual rows annotated at query
                    # completion (SET SESSION collect_stats = true) —
                    # the web UI's operator detail table
                    from presto_tpu import obs

                    tl = obs.timeline_for(parts[2])
                    ops = tl.annotation("operators") if tl is not None \
                        else None
                    if ops is None:
                        self._json(404, {"error": "no operator stats for "
                                                  f"query {parts[2]} (SET "
                                                  "SESSION collect_stats "
                                                  "= true)"})
                        return
                    self._json(200, {"queryId": parts[2],
                                     "operators": ops})
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "doctor":
                    # post-query diagnosis: findings stored at
                    # completion, else a fresh run over the registries
                    from presto_tpu import obs

                    if obs.timeline_for(parts[2]) is None \
                            and obs.lookup(parts[2]) is None \
                            and obs.progress_for(parts[2]) is None:
                        self._json(404, {"error": "no telemetry for "
                                                  f"query {parts[2]}"})
                        return
                    self._json(200, obs.doctor.report(parts[2]))
                    return
                if len(parts) == 4 and parts[:2] == ["v1", "statement"]:
                    qid, token = parts[2], int(parts[3])
                    q = outer.queries.get(qid)
                    if q is None:
                        self._json(404, {"error": "unknown query"})
                        return
                    # async pollers re-fetch the same token while the
                    # query runs; a short wait turns a hot poll loop
                    # into a long-poll without delaying finished pages
                    if not q.done.is_set():
                        q.done.wait(timeout=0.3)
                    self._json(200, outer._page_response(q, token))
                    return
                self._json(404, {"error": "not found"})

            def do_DELETE(self):
                parts = [p for p in self.path.split("/") if p]
                if len(parts) >= 3 and parts[:2] == ["v1", "statement"]:
                    q = outer.queries.get(parts[2])
                    if q is not None:
                        with outer._lock:
                            if q.state in ("QUEUED", "RUNNING"):
                                q.state = "CANCELED"
                                q.done.set()
                        # a queued victim's memory-gate wait exits at
                        # its next wakeup instead of running its bound,
                        # and a RUNNING victim's slot + projected bytes
                        # free immediately (once-only, same as a kill)
                        # rather than when the zombie thread unwinds
                        outer.admission.cancel(q.id)
                        outer._release_group(q)
                    self._json(204, {})
                    return
                self._json(404, {"error": "not found"})

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True,
                                        name="coordinator-http")

    # ------------------------------------------------------------------
    def start(self) -> None:
        self._thread.start()
        if self.memory_manager is not None:
            self.memory_manager.start()
        if self.worker_uris:
            self.failure_detector.start()
        # serving processes keep a metrics-history ring by default
        # (1s cadence unless PRESTO_TPU_METRICS_HISTORY_MS overrides);
        # only the server that armed the process singleton stops it
        from presto_tpu.obs.timeseries import HISTORY

        with self._lock:
            self._history_owner = (not HISTORY.running
                                   and HISTORY.start(default_ms=1000))

    def stop(self, drain_timeout: float = 30.0) -> None:
        self.failure_detector.stop()
        if self.memory_manager is not None:
            self.memory_manager.stop()
        from presto_tpu.obs.timeseries import HISTORY

        with self._lock:
            owner = getattr(self, "_history_owner", False)
            self._history_owner = False
        if owner:
            HISTORY.stop()
        if self._thread.is_alive():  # shutdown() blocks unless serving
            self.httpd.shutdown()
        self.httpd.server_close()
        # drain in-flight computation threads: cancellation is
        # cooperative (the thread discards its result but runs to the
        # end), and its per-query pool reservations release only at
        # completion — a stop() that abandons them leaks reservations
        # into whatever runs next in the process
        deadline = time.monotonic() + drain_timeout
        with self._lock:
            pending = [q.thread for q in self.queries.values()
                       if q.thread is not None]
        for t in pending:
            t.join(max(0.0, deadline - time.monotonic()))

    def _release_group(self, q: _QueryState) -> None:
        """Release a query's admission ticket EXACTLY once — callable
        from both the computation thread's finally and a killer (the
        deadline timer / memory manager), so a killed query frees its
        slot immediately instead of holding it until the cooperative
        thread unwinds.  The zombie thread may briefly run past the
        group's concurrency limit; that window is the same one the
        cooperative memory-kill protocol already accepts.  (The
        controller's release is itself once-only and additionally wakes
        memory-gate waiters — a finished query is when headroom
        reappears.)"""
        with self._lock:
            ticket = q.ticket
        self.admission.release(ticket)

    def _kill_query(self, qid: str) -> None:
        """LowMemoryKiller action: cancel through the normal state path
        (the computation thread discards its result on completion)."""
        q = self.queries.get(qid)
        if q is not None:
            with self._lock:
                if q.state in ("QUEUED", "RUNNING"):
                    q.state = "CANCELED"
                    q.error = "query killed by the cluster memory manager"
                    q.done.set()
            # a victim still waiting at the memory gate exits at its
            # next wakeup instead of holding its group slot for the
            # rest of the queue bound (same as the DELETE path)
            self.admission.cancel(qid)
            self._release_group(q)

    # -- deadlines ------------------------------------------------------
    def _effective_deadline(self) -> float:
        """Seconds a query may run: the ``query_max_execution_time``
        session property when set, else the coordinator's
        ``query.max-execution-time`` config default (0 = none)."""
        from presto_tpu.config import parse_duration

        try:
            prop = str(self.runner.session.get("query_max_execution_time"))
        except KeyError:
            prop = ""
        if prop.strip():
            return parse_duration(prop, self.max_execution_time)
        return self.max_execution_time

    def _blocking_wait(self, q: _QueryState) -> Optional[float]:
        """Bound for the legacy blocking POST: deadline + grace when
        that is tighter (the killer resolves the query within it),
        capped at the protocol's 600s long-poll bound — either way the
        response always arrives, carrying nextUri for a query still
        queued or running, so clients with their own socket timeouts
        (StatementClient's 650s default) never starve."""
        # prefer the limit the killer was actually ARMED with (set when
        # the query went RUNNING); fall back to the session-derived
        # value for still-queued queries
        limit = (q.deadline_s if q.deadline_s is not None
                 else self._effective_deadline())
        if limit and limit > 0:
            return min(600.0, limit + self.deadline_grace)
        return 600.0

    def _deadline_kill(self, q: _QueryState, limit: float) -> None:
        """Timer action at deadline expiry: fail the statement with
        EXCEEDED_TIME_LIMIT, free the query's memory reservations
        (poisoning future ones, so the computation thread unwinds at
        its next reservation), and emit the kill event."""
        with self._lock:
            if q.state != "RUNNING":
                return
            q.state = "FAILED"
            q.error_code = "EXCEEDED_TIME_LIMIT"
            q.error = (f"Query exceeded the maximum execution time of "
                       f"{limit:g}s (EXCEEDED_TIME_LIMIT)")
        pool = getattr(self.runner.executor, "memory_pool", None)
        if pool is not None:
            pool.kill_query(q.id)
        from presto_tpu.obs import METRICS

        METRICS.counter("query.killed_deadline").inc()
        elapsed = (round(time.monotonic() - q.t_running, 3)
                   if q.t_running is not None else None)
        try:
            from presto_tpu.events import QueryKilledEvent

            self.runner.events.query_killed(QueryKilledEvent(
                query_id=q.id, reason="EXCEEDED_TIME_LIMIT",
                message=q.error, limit_s=limit, elapsed_s=elapsed,
                kill_time=time.time()))
        except Exception:
            pass  # telemetry must never block the kill
        self._release_group(q)
        q.done.set()

    def worker_rows(self) -> List[dict]:
        """Failure-detector rows for /v1/worker and the
        system_runtime_workers table (NULL-safe columns)."""
        return self.failure_detector.snapshot()

    @property
    def uri(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    # ------------------------------------------------------------------
    def _submit(self, sql: str,
                trace_token: Optional[str] = None) -> _QueryState:
        qid = uuid.uuid4().hex[:16]
        q = _QueryState(qid, sql)
        q.trace_token = trace_token
        with self._lock:
            self.queries[qid] = q

        def run():
            from presto_tpu.resource_groups import QueryQueueFullError

            try:
                prio = int(self.runner.session.get("query_priority"))
            except Exception:
                prio = 0
            # the memory-aware admission gate (serving/admission.py):
            # group concurrency + queue quota + projected pool
            # headroom, bounded by query.max-queued-time.  Rejections
            # keep distinct statement error codes (QUERY_QUEUE_FULL /
            # EXCEEDED_QUEUE_TIME) instead of a generic failure.
            try:
                ticket = self.admission.admit(
                    q.id, self.runner.session.user, priority=prio,
                    timeout=(self.max_queued_time
                             if self.max_queued_time > 0 else None),
                    statement_key=sql)
                with self._lock:
                    q.ticket = ticket
            except QueryQueueFullError as e:
                self._admission_failed(q, "QUERY_QUEUE_FULL", e)
                return
            except TimeoutError as e:
                self._admission_failed(q, "EXCEEDED_QUEUE_TIME", e)
                return
            except Exception as e:
                self._admission_failed(q, None, e)
                return
            with self._lock:
                if q.state != "QUEUED":  # canceled while queued
                    pass  # fall through to the release below
                else:
                    q.state = "RUNNING"
                    q.t_running = time.monotonic()
            if q.state != "RUNNING":
                self._release_group(q)
                q.done.set()
                return
            # deadline enforcement (query.max-execution-time config /
            # query_max_execution_time session property): the killer
            # fails the statement, frees the query's memory
            # reservations and emits QueryKilledEvent with reason
            # EXCEEDED_TIME_LIMIT
            limit = self._effective_deadline()
            timer = None
            if limit > 0:
                q.deadline_s = limit
                timer = threading.Timer(
                    limit, self._deadline_kill, args=(q, limit))
                timer.daemon = True
                timer.start()
            try:
                res = self.runner.execute(sql, query_id=q.id,
                                          trace_token=q.trace_token)
                cols = [
                    {"name": n, "type": repr(t)} for n, t in zip(res.names, res.types)
                ]
                # per-run outcome rides the result object — reading the
                # shared runner._dist here would let concurrent queries
                # report each other's stats
                q.dist_stages = getattr(res, "dist_stages", None)
                q.dist_fallback = getattr(res, "dist_fallback", None)
                q.planning_ms = getattr(res, "planning_ms", None)
                q.compile_ms = getattr(res, "compile_ms", None)
                q.execution_ms = getattr(res, "execution_ms", None)
                q.host_reads = getattr(res, "host_reads", None)
                q.compacted_pages = getattr(res, "compacted_pages", None)
                q.compact_fallback_pages = getattr(
                    res, "compact_fallback_pages", None)
                q.expand_retries = getattr(res, "expand_retries", None)
                q.expanded_rows = getattr(res, "expanded_rows", None)
                q.arith_checked = getattr(res, "arith_checked", None)
                q.arith_proven = getattr(res, "arith_proven", None)
                q.chain_probes = getattr(res, "chain_probes", None)
                q.cache_hit = getattr(res, "cache_hit", None)
                q.queued_ms = getattr(res, "queued_ms", None)
                q.memory_blocked_ms = getattr(res, "memory_blocked_ms",
                                              None)
                q.findings = getattr(res, "findings", None)
                # observed peak feeds the admission controller's memory
                # projection for the NEXT run of this statement
                self.admission.record_peak(
                    sql, getattr(res, "peak_bytes", 0) or 0)
                # CANCELED is terminal: a DELETE that raced this query's
                # completion must not be resurrected to FINISHED/FAILED
                with self._lock:
                    if q.state == "RUNNING":
                        q.columns = cols
                        q.rows = res.rows
                        q.state = "FINISHED"
            except Exception as e:  # surfaces to the client as error
                with self._lock:
                    if q.state == "RUNNING":
                        q.error = f"{type(e).__name__}: {e}"
                        q.state = "FAILED"
            finally:
                if timer is not None:
                    timer.cancel()
                self._release_group(q)
                q.done.set()

        t = threading.Thread(target=run, daemon=True,
                             name=f"query-{q.id}")
        t.start()  # started before publication: stop() joins safely
        with self._lock:
            q.thread = t
        return q

    def _admission_failed(self, q: _QueryState, code: Optional[str],
                          e: Exception) -> None:
        """Fail a statement at the admission gate with its policy error
        code, emitting the kill-decision event so the query log records
        WHY the query never ran (queue full vs queue-time expiry)."""
        with self._lock:
            if q.state == "QUEUED":
                q.error = f"{type(e).__name__}: {e}"
                q.error_code = code
                q.state = "FAILED"
        if code is not None:
            try:
                from presto_tpu.events import QueryKilledEvent

                self.runner.events.query_killed(QueryKilledEvent(
                    query_id=q.id, reason=code, message=str(e),
                    limit_s=(self.max_queued_time
                             if code == "EXCEEDED_QUEUE_TIME" else None),
                    elapsed_s=None, kill_time=time.time()))
            except Exception:
                pass  # telemetry must never mask the failure
        q.done.set()

    def _cluster_stats(self) -> dict:
        """ClusterStatsResource analog (feeds the web UI tiles)."""
        with self._lock:
            states = [q.state for q in self.queries.values()]
        out = {
            "runningQueries": states.count("RUNNING"),
            "queuedQueries": states.count("QUEUED"),
            "finishedQueries": states.count("FINISHED"),
            "failedQueries": states.count("FAILED") + states.count("CANCELED"),
        }
        pool = getattr(self.runner.executor, "memory_pool", None)
        if pool is not None:
            out["reservedBytes"] = pool.reserved
            out["totalBytes"] = pool.limit
        return out

    def _page_response(self, q: _QueryState, token: int) -> dict:
        out = {
            "id": q.id,
            "columns": q.columns,
            "stats": {"state": q.state, "rows": len(q.rows)},
        }
        if q.dist_stages is not None:
            out["stats"]["distStages"] = q.dist_stages
        if q.dist_fallback is not None:
            out["stats"]["distFallback"] = q.dist_fallback
        # per-stage lifecycle times (sourced from the obs spans; NULL
        # keys simply absent, matching distStages' convention)
        if q.planning_ms is not None:
            out["stats"]["planningMs"] = q.planning_ms
        if q.compile_ms is not None:
            out["stats"]["compileMs"] = q.compile_ms
        if q.execution_ms is not None:
            out["stats"]["executionMs"] = q.execution_ms
        if q.host_reads is not None:
            out["stats"]["hostReads"] = q.host_reads
        if q.compacted_pages is not None:
            out["stats"]["compactedPages"] = q.compacted_pages
            out["stats"]["compactFallbackPages"] = q.compact_fallback_pages
        if q.expand_retries is not None:
            out["stats"]["expandRetries"] = q.expand_retries
            out["stats"]["expandedRows"] = q.expanded_rows
        if q.arith_checked is not None:
            out["stats"]["arithChecked"] = q.arith_checked
            out["stats"]["arithProven"] = q.arith_proven
        if q.chain_probes is not None:
            out["stats"]["chainProbes"] = q.chain_probes
        # serving tier: result provenance (structural result cache)
        if q.cache_hit is not None:
            out["stats"]["cacheHit"] = q.cache_hit
        # admission-plane waits (mirrors system_runtime_queries'
        # queued_ms/memory_blocked_ms columns; absent when NULL)
        if q.queued_ms is not None:
            out["stats"]["queuedMs"] = q.queued_ms
        if q.memory_blocked_ms is not None:
            out["stats"]["memoryBlockedMs"] = q.memory_blocked_ms
        # live queue position while waiting for admission (1-based;
        # also cached on the state object for /v1/query summaries)
        if q.state == "QUEUED":
            pos = self.admission.queue_position(q.id)
            q.queue_position = pos
            if pos is not None:
                out["stats"]["queuePosition"] = pos
        # Presto-style live progress (StatementStats.progressPercentage
        # + a per-stage split table).  Monotone by construction: the
        # progress object reports a running maximum, and a FINISHED
        # query always reads 100.
        from presto_tpu import obs

        prog = obs.progress_for(q.id)
        if q.state == "FINISHED":
            out["stats"]["progressPercentage"] = 100.0
        elif prog is not None:
            out["stats"]["progressPercentage"] = prog.percentage()
        if prog is not None:
            snap = prog.snapshot()
            out["stats"]["stages"] = snap["stages"]
            out["stats"]["elapsedMs"] = snap["elapsedMs"]
        if q.error:
            out["error"] = q.error
            # distinct statement error codes for policy failures
            # (QUERY_QUEUE_FULL / EXCEEDED_QUEUE_TIME /
            # EXCEEDED_TIME_LIMIT); generic failures carry none
            if q.error_code is not None:
                out["errorCode"] = q.error_code
            return out
        if q.state in ("QUEUED", "RUNNING"):
            # async page: no data yet — the client re-polls this token
            out["nextUri"] = f"{self.uri}/v1/statement/{q.id}/{token}"
            return out
        start = token * PAGE_ROWS
        chunk = q.rows[start : start + PAGE_ROWS]
        out["data"] = [list(r) for r in chunk]
        if start + PAGE_ROWS < len(q.rows):
            out["nextUri"] = f"{self.uri}/v1/statement/{q.id}/{token + 1}"
        return out

    # ------------------------------------------------------------------
    def remote_metrics(self) -> Dict[str, List]:
        """Poll every worker's ``/v1/metrics?format=json`` concurrently
        (net.poll_each; failures are classified, counted and
        transition-logged there — a dead worker's liveness itself is
        the failure detector's job) — the fan-in behind
        system_metrics' per-node rows and cluster rollup."""
        from presto_tpu.net import poll_each, request_json

        payloads = poll_each(
            self.worker_uris,
            lambda uri: request_json(
                f"{uri}/v1/metrics?format=json", timeout=2.0,
                site="cluster.metrics_poll_errors"),
            health=self._metrics_poll_health)
        return {
            payload.get("node") or uri: [
                (n, float(v)) for n, v in payload.get("metrics", [])]
            for uri, payload in payloads.items()
        }

    def remote_history(self) -> Dict[str, List]:
        """Poll every worker's ``/v1/metrics/history`` concurrently —
        the fan-in behind system_metrics_history's per-node rows and
        the coordinator's merged history endpoint."""
        from presto_tpu.net import poll_each, request_json

        payloads = poll_each(
            self.worker_uris,
            lambda uri: request_json(
                f"{uri}/v1/metrics/history", timeout=2.0,
                site="cluster.metrics_poll_errors"),
            health=self._history_poll_health)
        return {
            payload.get("node") or uri: [
                (float(ts), str(n), float(v))
                for ts, n, v in payload.get("rows", [])]
            for uri, payload in payloads.items()
        }

    def metrics_history(self) -> dict:
        """``GET /v1/metrics/history``: the local ring plus every
        worker's, keyed by node id (the cluster-merged twin of the
        worker endpoint's single-node body)."""
        from presto_tpu.obs.timeseries import HISTORY

        nodes: Dict[str, List] = {
            "local": [[ts, n, v] for ts, n, v in HISTORY.rows()]}
        if self.worker_uris:
            for node, rows in self.remote_history().items():
                nodes[node] = [[ts, n, v] for ts, n, v in rows]
        return {"intervalMs": HISTORY.interval_ms, "nodes": nodes}

    def memory_pool_rows(self) -> List[dict]:
        """system_memory_pools rows for this cluster: the local pool +
        every worker's ``/v1/info`` memory section (net.poll_each —
        same classification/transition-log contract as the metrics
        poll)."""
        from presto_tpu.connectors.system import pool_row
        from presto_tpu.net import poll_each, request_json

        rows: List[dict] = []
        pool = getattr(self.runner.executor, "memory_pool", None)
        if pool is not None:
            rows.append(pool_row("local", pool))
        infos = poll_each(
            self.worker_uris,
            lambda uri: request_json(f"{uri}/v1/info", timeout=2.0,
                                     site="cluster.memory_poll_errors"),
            health=self._memory_poll_health)
        for uri, info in infos.items():
            mem = info.get("memory") or {}
            rows.append({
                "node": uri,
                "reserved": int(mem.get("reserved", 0)),
                "peak": int(mem.get("peak", 0)),
                "limit": int(mem.get("limit", 0)),
                "queries": len(mem.get("query_reservations") or {}),
            })
        return rows
