"""Multi-host distributed execution over HTTP workers (the DCN tier).

Reference analog: the coordinator's distributed scheduling stack —
``SqlQueryScheduler.java:441`` (stage scheduling), split placement
(``scheduler/NodeScheduler.java``), ``HttpRemoteTask.java:99`` with
``RequestErrorTracker``/``Backoff`` (transient RPC tolerance), and
``failureDetector/HeartbeatFailureDetector.java:77`` (exclude dead
nodes from scheduling).

TPU framing: the ICI tier (parallel/dist.py) shards a query across the
chips of one slice; THIS tier fans leaf fragments out across hosts
(each host owning its own slice/chip) and merges partial aggregation
states at the coordinator — i.e. the cross-slice exchange rides DCN as
serialized partial-state pages, while intra-fragment work stays
all-XLA on each host.  Unlike the reference (any task failure fails
the query, SURVEY.md §2.2 recovery row), leaf fragments here are pure
functions of (table, splits), so a failed worker's splits are
re-scheduled on the survivors.
"""

from __future__ import annotations

import itertools
import json
import logging
import threading
import time

import numpy as np
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence

from presto_tpu.analysis.protocols import RECORDER
from presto_tpu.catalog import Catalog
from presto_tpu.exec.chain import chain_leaf
from presto_tpu.exec.local import LocalRunner, MaterializedResult, concat_pages_device
from presto_tpu.planner.plan import (
    AggregationNode,
    FilterNode,
    LimitNode,
    OutputNode,
    PlanNode,
    PrecomputedNode,
    ProjectNode,
    SortNode,
    TableScanNode,
    TopNNode,
    WindowNode,
)
from presto_tpu.server.serde import deserialize_page, plan_to_json
from presto_tpu.sync import named_lock

_log = logging.getLogger("presto_tpu.multihost")

#: distinguishes concurrent failover drains in one process — each gets
#: its own retry spec-automaton run (conformance tracing only)
_FAILOVER_SEQ = itertools.count(1)


class TaskFailed(Exception):
    """The remote task hit a deterministic query error (its fragment
    raised) — distinct from worker/transport failure, so the caller
    neither retries nor excludes the worker."""


class _StageCapacity(Exception):
    """A stage-2 task overflowed its group capacity; the caller doubles
    max_groups and re-runs the stage."""


#: error-text markers that mean a WORKER/transport fault (fall back to
#: a degraded path) rather than a deterministic query failure
_TRANSPORT_MARKERS = ("URLError", "Connection refused", "ConnectionRefused",
                      "RemoteDisconnected", "TimeoutError", "timed out",
                      "no progress",
                      # CRC damage in a worker-to-worker shuffle pull
                      # surfaces inside the stage-2 task's error text;
                      # the fragment is pure, so it recomputes (net.py
                      # classifies PageIntegrityError transient)
                      "PageIntegrityError")


class MultiHostUnsupported(Exception):
    pass


class _StreamBroken(ConnectionError):
    """A producing worker died mid-stream AFTER the consumer took
    ``delivered`` pages: the failover re-run must replay from that
    watermark (skip the first ``delivered`` pages) instead of
    recomputing into duplicates — the streaming twin of the
    all-or-nothing fragment retry."""

    def __init__(self, delivered: int, cause: BaseException):
        super().__init__(f"{type(cause).__name__}: {cause}")
        self.delivered = delivered


class WorkerClient:
    """One remote worker (HttpRemoteTask + Backoff analog). Results
    stream through the worker's acked pull buffers: long-poll GETs with
    token acknowledgement (ExchangeClient/HttpPageBufferClient.java:291
    sendGetResults + .../acknowledge), so large shuffles never hold a
    whole task's output in one response and the producer sees
    backpressure from unacknowledged bytes."""

    def __init__(self, uri: str, max_attempts: int = 3, timeout: float = 300.0,
                 detector=None):
        self.uri = uri.rstrip("/")
        self.max_attempts = max_attempts
        self.timeout = timeout
        self.alive = True
        # failure detector sink (parallel/failure.py): every real
        # protocol outcome feeds the same state machine the background
        # heartbeat does, so the circuit breaker sees fragment traffic
        self.detector = detector
        # request-correlation token stamped by the runner before a
        # fan-out (X-Presto-Trace-Token, the reference's
        # GenerateTraceTokenRequestFilter contract): every task POST
        # carries it so worker-side spans stitch into the query's trace
        self.trace_token: Optional[str] = None
        # estimate-vs-actual roll-up: when the runner installs a sink,
        # every task POST asks the worker to record per-operator
        # actuals, and delete_task fetches the FINISHED task's stats
        # snapshot before dropping it — delete is the one chokepoint
        # every task path (streamed, two-stage, retried) goes through
        self.collect_stats = False
        self.stats_sink = None  # (task id, wire entries) -> None

    def _ok(self) -> None:
        self.alive = True
        if self.detector is not None:
            self.detector.record_success(self.uri)

    def _failed(self, exc: BaseException) -> None:
        self.alive = False
        if self.detector is not None:
            self.detector.record_failure(
                self.uri, f"{type(exc).__name__}: {exc}")

    def ping(self, timeout: float = 5.0) -> bool:
        """Heartbeat probe with CLASSIFIED failure handling: each
        failure increments the per-reason net.errors_* counters and
        worker.ping_errors; state-transition logging is the failure
        detector's (once per edge, never per poll)."""
        from presto_tpu.net import request_json

        try:
            # site= counts ONCE per failure (worker.ping_errors +
            # net.errors_<reason>) inside the request helper
            request_json(f"{self.uri}/v1/info", timeout=timeout,
                         site="worker.ping_errors")
            self._ok()
        except Exception as e:
            self._failed(e)
        return self.alive

    def run_fragment(self, fragment_json: dict) -> List[bytes]:
        from presto_tpu.net import is_transient

        last: Optional[Exception] = None
        for attempt in range(self.max_attempts):
            try:
                # a fresh task id per attempt: fragments are pure, so a
                # retried task simply recomputes (at-least-once overall,
                # de-duplicated by task id server-side)
                out = self._pull_task(fragment_json)
                self._ok()
                return out
            except TaskFailed:
                # a deterministic query error, NOT a worker fault:
                # retrying recomputes the same failure and blaming the
                # worker would poison failover
                raise
            except Exception as e:
                if not is_transient(e):
                    # deterministic by classification (net.py): never
                    # retried, never blamed on the worker
                    raise TaskFailed(f"{type(e).__name__}: {e}") from e
                last = e
                time.sleep(min(0.1 * (2 ** attempt), 2.0))
        self._failed(last)
        raise ConnectionError(f"worker {self.uri} failed: {last}")

    def create_task(self, fragment_json: dict,
                    output_spec: Optional[dict] = None) -> str:
        """POST a task and return its id WITHOUT pulling results — the
        two-stage path's stage-1 tasks are drained by stage-2 workers,
        not by the coordinator (HttpRemoteTask's create half)."""
        import uuid

        tid = uuid.uuid4().hex[:16]
        body_dict = {"fragment": fragment_json}
        if output_spec is not None:
            body_dict["output"] = output_spec
        if self.collect_stats:
            body_dict["collect_stats"] = True
        body = json.dumps(body_dict).encode()
        headers = {"Content-Type": "application/json"}
        if self.trace_token:
            headers["X-Presto-Trace-Token"] = self.trace_token
        req = urllib.request.Request(
            f"{self.uri}/v1/task/{tid}", data=body, method="POST",
            headers=headers,
        )
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            json.load(resp)
        return tid

    def pull_results(self, tid: str) -> List[bytes]:
        """Drain buffer 0 of an already-created task (the pull half).
        Task failure surfaces through the pull itself: a failed task's
        buffer answers 500 with the error payload, and pull_pages also
        consults /v1/task/{id} on error (the continuous status
        fetcher's role, ContinuousTaskStatusFetcher analog, without a
        dedicated polling thread per pull)."""
        from presto_tpu.net import PageIntegrityError
        from presto_tpu.server.serde import verify_page
        from presto_tpu.server.shuffle_client import TaskPullFailed, pull_pages

        try:
            raws = list(pull_pages(self.uri, tid, 0, timeout=self.timeout))
        except TaskPullFailed as e:
            if "PageIntegrityError" in str(e):
                # the task failed because its INPUT page arrived
                # damaged — a transport fault, not a query error.
                # Retrying is safe for every fragment run_fragment
                # ships (scan-leaf and pre-chunk inputs travel INSIDE
                # the fragment, so a retry re-serializes fresh bytes);
                # RemoteSource consumers never come through here —
                # they run via _fan_out_stage2, whose transport-marker
                # triage falls back to a coordinator-merge that
                # recomputes from base tables rather than re-pulling a
                # drained upstream buffer
                raise PageIntegrityError(str(e)) from e
            raise TaskFailed(str(e)) from e
        for r in raws:
            # CRC check at the pull boundary: a damaged page raises
            # PageIntegrityError (transient) HERE, inside the caller's
            # retry loop, instead of poisoning the stage-level decode
            verify_page(r)
        return raws

    def delete_task(self, tid: str) -> None:
        if self.stats_sink is not None:
            # fetch-before-delete: only a FINISHED task's snapshot
            # merges (a retried attempt's partial stats would double-
            # count rows the fresh attempt recounts); best-effort like
            # the delete itself
            try:
                req = urllib.request.Request(f"{self.uri}/v1/task/{tid}")
                with urllib.request.urlopen(req, timeout=10.0) as resp:
                    status = json.load(resp)
                if status.get("state") == "FINISHED" and status.get("stats"):
                    self.stats_sink(tid, status["stats"])
            except Exception:
                pass
        try:
            req = urllib.request.Request(
                f"{self.uri}/v1/task/{tid}", method="DELETE")
            urllib.request.urlopen(req, timeout=10.0).close()
        except Exception:
            pass

    def _pull_task(self, fragment_json: dict) -> List[bytes]:
        """create + drain + delete, composed from the shared protocol
        pieces (one implementation of the token/ack long-poll loop:
        server/shuffle_client.pull_pages)."""
        tid = self.create_task(fragment_json)
        try:
            return self.pull_results(tid)
        finally:
            self.delete_task(tid)


class MultiHostRunner:
    """Fans leaf-fragment execution out to HTTP workers.

    Supported plan shape (same as DistributedRunner): post-agg nodes
    over a single-step aggregation over a scan-rooted chain.  The
    chain + partial aggregation run on workers over disjoint split
    assignments; final merge + post-processing run at the coordinator.
    """

    def __init__(self, catalog: Catalog, worker_uris: Sequence[str],
                 broadcast_threshold: Optional[int] = None,
                 worker_locations: Optional[dict] = None,
                 max_splits_per_node: int = 0,
                 execution_policy: str = "phased",
                 detector=None, events=None,
                 max_fragment_retries: Optional[int] = None,
                 exchange_streaming: Optional[bool] = None):
        from presto_tpu.parallel.failure import FailureDetector
        from presto_tpu.parallel.fragment import DEFAULT_BROADCAST_THRESHOLD

        self.catalog = catalog
        # failure detector: one state machine per worker, fed by every
        # ping AND every real fragment outcome; DEAD workers are
        # excluded from assignment until their backoff window lets one
        # optimistic probe through (the circuit breaker)
        self.detector = detector or FailureDetector(worker_uris)
        if events is not None:
            import time as _time

            from presto_tpu.events import WorkerStateChangeEvent

            self.detector.add_transition_listener(
                lambda uri, old, new, reason: events.worker_state_changed(
                    WorkerStateChangeEvent(
                        uri=uri, old_state=old, new_state=new,
                        reason=reason, change_time=_time.time())))
        self.workers = [WorkerClient(u, detector=self.detector)
                        for u in worker_uris]
        # per-stage fragment re-dispatch budget: bounds how long a
        # query chases a flapping cluster before the coordinator-local
        # fallback finishes the work itself
        self.max_fragment_retries = (max(4, 2 * len(self.workers))
                                     if max_fragment_retries is None
                                     else max_fragment_retries)
        # the coordinator-local fallback (and glue execution) runs its
        # scan splits through the morsel scheduler like every other
        # LocalRunner; worker-side fragments get it inside
        # server/worker.py's runner (same exec/tasks.py pool knobs)
        self.local = LocalRunner(catalog)
        self.broadcast_threshold = (DEFAULT_BROADCAST_THRESHOLD
                                    if broadcast_threshold is None
                                    else broadcast_threshold)
        # scheduling policies (scheduler.py): split placement locality
        # keyed by worker URI, per-node split backpressure, and the
        # build-before-probe stage launch ordering
        if execution_policy not in ("phased", "all_at_once"):
            raise ValueError(
                f"execution_policy must be 'phased' or 'all_at_once', "
                f"got {execution_policy!r}")
        locs = {k.rstrip("/"): v for k, v in (worker_locations or {}).items()}
        self.worker_locations = {w: locs.get(w.uri) for w in self.workers}
        self.max_splits_per_node = max_splits_per_node
        self.execution_policy = execution_policy
        # stage-DAG knobs/observability (mirrors DistributedRunner)
        from presto_tpu.parallel.fragment import DEFAULT_MIN_STAGE_ROWS
        from presto_tpu.parallel.streams import (
            exchange_buffer_bytes_default, exchange_streaming_default,
        )

        self.min_stage_rows = DEFAULT_MIN_STAGE_ROWS
        # streaming page exchange (parallel/streams.py): worker pages
        # reach the consumer as they land in the producer's output
        # buffer; off = drain-everything-then-continue (the A/B leg)
        self.exchange_streaming = (exchange_streaming_default()
                                   if exchange_streaming is None
                                   else bool(exchange_streaming))
        self.exchange_buffer_bytes = exchange_buffer_bytes_default()
        self.merge_fanin = 8
        # stage-overlap evidence of the last streamed gather (A/B tool)
        self.last_exchange_stats: Dict[str, float] = {}
        self.last_stage_count = 0
        self.last_gather_rows = 0
        # observability: last split placement per stage-launch
        # ({worker uri: [split ids]})
        self.last_assignments: Dict[str, List[int]] = {}
        # local-execution fallback accounting (VERDICT weak #8: the
        # silent MultiHostUnsupported catch hid that queries never
        # left the coordinator) — mirrors DistributedRunner's loud
        # fallback contract and feeds system_runtime_queries /
        # query-JSON stats
        self.fallback_count = 0
        self.last_fallback_reason: Optional[str] = None
        # estimate-vs-actual plane: a caller-provided QueryStats that
        # worker-task snapshots merge into (see run()); None = off
        self.stats = None

    def run(self, plan: PlanNode, stats=None) -> MaterializedResult:
        from presto_tpu.obs import METRICS, current_tracer

        self.last_gather_rows = 0  # rows pulled to the coordinator
        self.last_stage_count = 0
        self.last_fallback_reason = None
        # stamp the active query's trace token on every worker client
        # so fan-out task POSTs carry X-Presto-Trace-Token and the
        # distributed stages stitch into one trace (best-effort under
        # concurrency: the token is per-runner, like last_assignments)
        tr = current_tracer()
        token = tr.trace_token if tr is not None else None
        # distributed actuals roll-up: workers record per-operator
        # stats (one device sync per page — opt-in), and every task's
        # FINISHED snapshot merges here by structural key.  Dedupe by
        # task id: retried fragments use fresh tids, but a double
        # delete of one tid must not double-count.
        qstats = stats if stats is not None else self.stats
        if qstats is not None:
            qstats.register_plan(plan)  # idempotent — shared key space
        seen_tids = set()

        def sink(tid: str, entries) -> None:
            if tid in seen_tids:
                return
            seen_tids.add(tid)
            qstats.merge_wire(entries)

        for w in self.workers:
            w.trace_token = token
            w.collect_stats = qstats is not None
            w.stats_sink = sink if qstats is not None else None
        if qstats is not None:
            # coordinator-side halves (glue breakers, residual root,
            # final merges) record through the local runner's per-
            # thread sink on THIS thread
            self.local.stats = qstats
        try:
            # per-run outcome rides the RESULT (dist_stages attached by
            # _run_distributed from its local stage count): concurrent
            # queries on one runner must not swap each other's stats
            out = self._run_distributed(plan, qstats)
            out.dist_fallback = None
            # per-run count off the RESULT, not the shared field a
            # concurrent run may have reset (same rule as dist_stages)
            METRICS.counter("multihost.stages_total").inc(
                out.dist_stages or 0)
            return out
        except MultiHostUnsupported as e:
            reason = str(e) or type(e).__name__
            self.last_fallback_reason = reason
            self.fallback_count += 1
            METRICS.counter("multihost.fallbacks").inc()
            _log.warning(
                "multi-host execution fell back to local: %s", reason)
            out = self.local.run(plan)
            out.dist_stages = 0
            out.dist_fallback = reason
            return out
        finally:
            if qstats is not None:
                self.local.stats = None
            for w in self.workers:
                w.collect_stats = False
                w.stats_sink = None

    def _live_workers(self) -> List["WorkerClient"]:
        """Workers eligible for fragment assignment: the failure
        detector's circuit breaker skips DEAD workers whose backoff
        window has not elapsed (no connect attempt at all), and a ping
        confirms the rest — feeding the same detector, so a recovered
        worker re-admits here."""
        alive = []
        for w in self.workers:
            if not self.detector.is_schedulable(w.uri) \
                    and not self.detector.probe_due(w.uri):
                continue  # circuit open: skip without a connect attempt
            # the ping feeds the detector; the SECOND is_schedulable
            # check enforces recover_after — a DEAD worker's first
            # successful probe leaves it DEAD (not yet re-admitted),
            # so placement waits for sustained recovery
            if w.ping() and self.detector.is_schedulable(w.uri):
                alive.append(w)
        return alive

    # ------------------------------------------------------------------
    def _run_distributed(self, plan: PlanNode,
                         qstats=None) -> MaterializedResult:
        """Generalized stage-DAG execution at the DCN tier — the same
        bottom-up ``lower_stages`` decomposition the mesh tier runs
        (PlanFragmenter.java:84 + SqlQueryScheduler.java:441):
        aggregation stages and streaming-chain stages execute as HTTP
        worker fragments (leaves are table scans OR re-chunked
        materialized intermediates of earlier stages), glue breakers
        (sort/union/limit/window) evaluate on the coordinator between
        stages, and the residual root runs locally over the spliced
        results."""
        from presto_tpu.parallel.fragment import (
            lower_stages, set_child, undistributable_reason,
        )

        def staged(node, run):
            """Run one stage, recording its output rows onto the
            ORIGINAL plan node when nothing else did: worker fragments
            whose root is structurally the coordinator's node (chain
            stages) already merged by key, but rebuilt-shape stages
            (partial/final agg splits, per-shard window/sort) report
            under their own signatures — the stage boundary is the one
            place the original node's actual is observable."""
            t0 = time.perf_counter()
            page = run()
            if qstats is not None and qstats.actual_rows(node) is None:
                rows = int(np.asarray(page.row_mask).sum())
                try:
                    from presto_tpu.memory import page_bytes
                    nb = page_bytes(page)
                except Exception:
                    nb = 0
                qstats.record(node, time.perf_counter() - t0, rows, nb)
            return page

        def run_agg(node: AggregationNode) -> PrecomputedNode:
            page = staged(node, lambda: self._stage_agg(node))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_chain(node: PlanNode, bound=None) -> PrecomputedNode:
            page = staged(node, lambda: self._stage_chain(node, bound))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def eval_glue(node: PlanNode) -> PrecomputedNode:
            # runs through self.local on this thread — the per-thread
            # stats sink records it like any local operator
            page = self.local.run_to_page(node)
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_window(node) -> PrecomputedNode:
            page = staged(node, lambda: self._stage_window(node))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_sort(node) -> PrecomputedNode:
            page = staged(node, lambda: self._stage_sort(node))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_union(node) -> PrecomputedNode:
            page = staged(node, lambda: self._stage_union(node))
            return PrecomputedNode(page=page, channel_list=node.channels)

        splices: List = []
        try:
            n_stages, root = lower_stages(
                plan, run_agg, run_chain, eval_glue, splices,
                min_stage_rows=self.min_stage_rows,
                run_window=run_window, run_sort=run_sort,
                run_union=run_union)
            if n_stages == 0:
                raise MultiHostUnsupported(undistributable_reason(plan))
            self.last_stage_count = n_stages
            out = self.local.run(root)
            if root is not plan:
                out.names, out.types = plan.output_names, plan.output_types
            # per-run stage count from the LOCAL n_stages, not the
            # shared field a concurrent run may have reset
            out.dist_stages = n_stages
            return out
        finally:
            for parent, slot, old in reversed(splices):
                set_child(parent, slot, old)

    # -- stage executors ------------------------------------------------
    def _stage_agg(self, agg: AggregationNode):
        """Aggregation stage: scan-leaf chains go through the two-stage
        worker shuffle / coordinator-merge retry machinery; chains over
        a materialized intermediate run worker-side partials over
        re-chunked input with a coordinator merge."""
        if agg.step != "single":
            raise MultiHostUnsupported("non-single aggregation stage")
        if any(a.fn == "evaluate_classifier_predictions" for a in agg.aggs):
            raise MultiHostUnsupported(
                "evaluate_classifier_predictions is local-only")
        from presto_tpu.obs import span

        leaf = chain_leaf(agg.source)
        with span("mh_stage:aggregation", cat="exchange"):
            if isinstance(leaf, TableScanNode):
                return self._run_agg_with_retry(agg, leaf)
            if isinstance(leaf, PrecomputedNode):
                return self._run_agg_over_pre(agg, leaf)
            raise MultiHostUnsupported("aggregation stage leaf is neither "
                                       "scan nor materialized input")

    def _stage_chain(self, chain_root: PlanNode, bound=None):
        """Streaming-chain stage (SOURCE fragment).  A consuming
        TopN/Limit ``bound`` ships as part of the fragment so each
        WORKER truncates to ``count`` rows before the gather moves
        O(workers x count) rows instead of the full selectivity
        (CreatePartialTopN.java / per-shard bound at the DCN tier);
        the coordinator's own bound node still does the global pick."""
        from presto_tpu.page import concat_pages_host

        leaf = chain_leaf(chain_root)
        frag: PlanNode = chain_root
        if isinstance(bound, TopNNode):
            frag = TopNNode(source=chain_root,
                            sort_exprs=list(bound.sort_exprs),
                            ascending=list(bound.ascending),
                            count=bound.count,
                            nulls_first=bound.nulls_first)
        elif isinstance(bound, LimitNode):
            frag = LimitNode(source=chain_root, count=bound.count)
        from presto_tpu.obs import span

        with span("mh_stage:chain", cat="exchange"):
            if isinstance(leaf, TableScanNode):
                pages = self._run_fragments(frag, leaf)
            elif isinstance(leaf, PrecomputedNode):
                pages = self._run_fragments_pre(frag, leaf)
            else:
                raise MultiHostUnsupported("chain stage leaf is neither "
                                           "scan nor materialized input")
        for p in pages:
            self.last_gather_rows += int(np.asarray(p.row_mask).sum())
        if not pages:  # an empty intermediate produced zero chunks
            from presto_tpu.page import Page

            return Page.empty([c.type for c in chain_root.channels], 1)
        return concat_pages_host(pages)

    def _stage_window(self, wnode: WindowNode):
        """Distributed window stage: stage-1 tasks run the source chain
        with hash-partitioned output on the PARTITION BY keys (one
        buffer per consumer — PartitionedOutputBuffer); stage-2 worker
        k pulls partition k from EVERY stage-1 task while stage 1 is
        still producing (the streaming stage overlap) and runs
        ``ops/window.py`` over its complete partitions; the coordinator
        drains only the window outputs.  Degrades to a distributed
        source gather + coordinator window when fewer than two workers
        survive or the shuffle dies mid-flight."""
        from presto_tpu.obs import span

        leaf = chain_leaf(wnode.source)
        with span("mh_stage:window", cat="exchange"):
            alive = self._live_workers()
            if len(alive) >= 2 and isinstance(leaf, TableScanNode):
                try:
                    return self._run_window_two_stage(wnode, leaf, alive)
                except ConnectionError as e:
                    # degrade below (gather + coordinator window) — loud:
                    # the operator must be able to see stage-1 re-scans
                    from presto_tpu.obs import METRICS

                    METRICS.counter(
                        "multihost.window_shuffle_degraded").inc()
                    _log.warning(
                        "window shuffle lost a worker mid-flight (%s); "
                        "degrading to gather + coordinator window", e)
            src_page = self._stage_chain(wnode.source)
            pre = PrecomputedNode(page=src_page,
                                  channel_list=wnode.source.channels)
            orig = wnode.source
            try:
                wnode.source = pre
                return self.local.run_to_page(wnode)
            finally:
                wnode.source = orig

    def _run_window_two_stage(self, wnode: WindowNode, scan: TableScanNode,
                              alive: List["WorkerClient"]):
        from presto_tpu.page import Page, concat_pages_host
        from presto_tpu.planner.plan import RemoteSourceNode

        kidx = [e.index for e in wnode.partition_exprs]
        kd = wnode.partition_domains
        stage1 = self._launch_stage1(wnode.source, scan, kidx, kd, alive)
        stage2: List[tuple] = []
        try:
            upstream = [(w.uri, tid) for w, tid in stage1]
            final = WindowNode(
                source=RemoteSourceNode(producer=wnode.source,
                                        tasks=upstream, buffer_id=0),
                partition_exprs=list(wnode.partition_exprs),
                order_exprs=list(wnode.order_exprs),
                ascending=list(wnode.ascending),
                funcs=list(wnode.funcs),
                func_names=list(wnode.func_names),
            )
            base = plan_to_json(final)

            def make_frag(k: int) -> dict:
                frag = json.loads(json.dumps(base))
                _set_remote_buffers(frag, k)
                return frag

            results = self._fan_out_stage2(alive, make_frag, stage2)
            dicts = [c.dictionary for c in wnode.channels]
            pages = [deserialize_page(r, dicts, verify=False)
                     for r in results]
            if not pages:
                return Page.empty([c.type for c in wnode.channels], 1)
            return concat_pages_host(pages)
        finally:
            for w, tid in stage1 + stage2:
                w.delete_task(tid)

    def _stage_sort(self, snode: SortNode):
        """Distributed ORDER BY: each worker's fragment sorts its own
        split subset (the SortNode ships inside the fragment), sorted
        runs stream back, and the coordinator finishes with the k-way
        order-preserving merge (ops/merge.py) — it never re-sorts the
        full relation."""
        from presto_tpu.obs import span
        from presto_tpu.ops.merge import merge_sorted_pages
        from presto_tpu.page import Page

        leaf = chain_leaf(snode.source)
        with span("mh_stage:sort", cat="exchange"):
            if isinstance(leaf, TableScanNode):
                pages = self._run_fragments(snode, leaf)
            elif isinstance(leaf, PrecomputedNode):
                pages = self._run_fragments_pre(snode, leaf)
            else:
                raise MultiHostUnsupported("sort stage leaf is neither "
                                           "scan nor materialized input")
        for p in pages:
            self.last_gather_rows += int(np.asarray(p.row_mask).sum())
        if not pages:
            return Page.empty([c.type for c in snode.channels], 1)
        sort_args = (list(snode.sort_exprs), list(snode.ascending),
                     snode.nulls_first)
        # fold in exchange_merge_fanin-sized batches so each k-way
        # merge's k (and its resident runs) stays bounded
        runs = list(pages)
        while len(runs) > self.merge_fanin:
            runs = [merge_sorted_pages(runs[i:i + self.merge_fanin],
                                       *sort_args)
                    for i in range(0, len(runs), self.merge_fanin)]
        return merge_sorted_pages(runs, *sort_args)

    def _stage_union(self, unode):
        """UNION legs as concurrent producer stages draining into ONE
        streaming exchange: leg k's pages carry its dictionary-code
        offsets; the consumer applies them and concatenates in leg
        order.  With exchange_streaming off the legs run sequentially
        (the materialized A/B leg)."""
        from presto_tpu.obs import span
        from presto_tpu.page import Page, concat_pages_host
        from presto_tpu.parallel.fragment import (
            is_agg_stage, remap_union_leg_page,
        )
        from presto_tpu.parallel.streams import (
            StreamingExchange, page_nbytes,
        )

        chans = unode.channels
        offsets = unode.code_offsets
        with span("mh_stage:union", cat="exchange"):
            ex = StreamingExchange(
                "union", "mh:union", streaming=self.exchange_streaming,
                max_bytes=self.exchange_buffer_bytes)
            stream = ex.stream(producers=len(unode.inputs))

            def make_producer(k: int, leg: PlanNode):
                def produce(st):
                    if is_agg_stage(leg, self.min_stage_rows):
                        page = self._stage_agg(leg)
                    else:
                        page = self._stage_chain(leg)
                    st.put((k, page), nbytes=page_nbytes(page))

                return produce

            for k, leg in enumerate(unode.inputs):
                ex.run(stream, make_producer(k, leg))
            by_leg: Dict[int, List] = {}
            try:
                for k, p in stream.drain():
                    by_leg.setdefault(k, []).append(
                        remap_union_leg_page(p, offsets[k], chans))
            except BaseException:
                ex.abort()
                raise
            finally:
                ex.join()
            out = [p for k in sorted(by_leg) for p in by_leg[k]]
            if not out:
                return Page.empty([c.type for c in chans], 1)
            return concat_pages_host(out)

    def _run_agg_over_pre(self, agg: AggregationNode, pre: PrecomputedNode):
        """Distributed aggregation whose input is a previous stage's
        materialized output: re-chunk the page across workers, run the
        partial aggregation worker-side, merge on the coordinator with
        the usual truncation-detect-and-double protocol."""
        from presto_tpu.exec.local import MAX_AGG_GROUPS, GroupCapacityExceeded

        mg = self.local._max_groups(agg)
        check = bool(agg.group_exprs) and not self.local._exact_capacity(
            agg, mg)
        while True:
            partial = AggregationNode(
                source=agg.source, group_exprs=agg.group_exprs,
                group_names=agg.group_names, aggs=agg.aggs,
                agg_names=agg.agg_names, step="partial", max_groups=mg,
            )
            pages = self._run_fragments_pre(partial, pre)
            if not pages:  # empty intermediate: no partial states
                from presto_tpu.page import Page

                pages = [Page.empty([c.type for c in partial.channels], 1)]
            if check and any(
                int(np.asarray(p.row_mask).sum()) >= mg for p in pages
            ):
                if mg >= MAX_AGG_GROUPS:
                    raise RuntimeError("aggregation capacity ceiling")
                mg *= 2
                continue
            merge_mg = mg
            while True:
                final = AggregationNode(
                    source=PrecomputedNode(
                        page=concat_pages_device(pages),
                        channel_list=partial.channels,
                    ),
                    group_exprs=[_key_ref(partial, i)
                                 for i in range(len(agg.group_exprs))],
                    group_names=agg.group_names, aggs=agg.aggs,
                    agg_names=agg.agg_names, step="final",
                    max_groups=merge_mg,
                )
                try:
                    return self.local._execute_to_page(final)
                except GroupCapacityExceeded:
                    if merge_mg >= MAX_AGG_GROUPS:
                        raise RuntimeError("aggregation capacity ceiling")
                    merge_mg *= 2

    def _run_fragments_pre(self, fragment_root: PlanNode,
                           pre: PrecomputedNode) -> List["Page"]:
        """Ship a fragment whose chain leaf is a materialized page:
        the page re-chunks row-wise across live workers and each chunk
        travels INSIDE its worker's fragment (serde "pre" node).  A
        failed worker's chunk re-runs on a survivor; with no survivors
        (or a spent retry budget) remaining chunks run on the
        coordinator — the fragment is pure, so local execution is
        always a correct last resort."""
        alive = self._live_workers()
        if not alive:
            raise MultiHostUnsupported("no live workers")
        chunks = _chunk_page(pre.page, len(alive))
        dictionaries = [c.dictionary for c in fragment_root.channels]

        results: List[bytes] = []
        lock = named_lock("multihost._run_fragments_pre.lock")
        failed: List[tuple] = []

        def make_fragment(chunk) -> dict:
            original = pre.page
            try:
                pre.page = chunk
                return plan_to_json(fragment_root)
            finally:
                pre.page = original

        if self.exchange_streaming:
            return self._stream_fragment_pairs(
                fragment_root, list(zip(alive, chunks)), make_fragment,
                run_local=lambda chunk, skip: self._run_chunk_local(
                    fragment_root, pre, chunk)[skip:])

        errors: List[BaseException] = []
        # timeline captured on the scheduling thread: run_on executes on
        # mh-chunk-* threads, which never inherit the recording TLS
        from presto_tpu.obs import current_timeline

        tl = current_timeline()

        def run_on(w: WorkerClient, chunk, fragment: dict):
            t0 = time.perf_counter()
            try:
                raws = w.run_fragment(fragment)
                with lock:
                    results.extend(raws)
                if tl is not None:
                    tl.extend("fragment_ms", w.uri,
                              (time.perf_counter() - t0) * 1e3)
            except ConnectionError:
                with lock:
                    failed.append(chunk)
            except BaseException as e:  # deterministic query error:
                with lock:              # must FAIL the query, not drop
                    errors.append(e)    # the chunk's rows silently

        def launch(pairs):
            # daemon + named (sanitizer thread-leak/unnamed-thread): a
            # worker POST wedged past its timeouts must not pin
            # interpreter exit, and reports need attributable names
            threads = [
                threading.Thread(target=run_on, args=(w, c,
                                                      make_fragment(c)),
                                 daemon=True, name=f"mh-chunk-{i}")
                for i, (w, c) in enumerate(pairs) if c is not None
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        launch(list(zip(alive, chunks)))
        local_pages = self._failover(
            failed, alive, errors,
            # rotate retried chunks across survivors, not just [0]
            lambda chunk, survivors, rr: launch(
                [(survivors[rr % len(survivors)], chunk)]),
            lambda chunk: self._run_chunk_local(fragment_root, pre, chunk))

        return [deserialize_page(r, dictionaries, verify=False)
                for r in results] + local_pages

    def _run_agg_with_retry(self, agg: AggregationNode, scan: TableScanNode):
        """Grouped aggregations with >=2 live workers run the full
        two-stage shuffle (partial on all workers -> hash-partitioned
        final on all workers, coordinator receives only the root);
        otherwise (or on worker failure mid-shuffle) the
        coordinator-merge fallback below.  A chain containing a join
        whose build side is too large to broadcast repartitions BOTH
        join sides across workers first (the DCN shuffle join)."""
        alive = self._live_workers()
        if len(alive) >= 2:
            join = self._partitionable_join(agg.source)
            if join is not None:
                try:
                    return self._run_agg_partitioned_join(agg, join, alive)
                except ConnectionError:
                    pass  # workers died mid-shuffle; fall back
        if agg.group_exprs and len(alive) >= 2:
            try:
                return self._run_agg_two_stage(agg, scan, alive)
            except ConnectionError:
                pass  # workers died mid-shuffle; fall back
        return self._run_agg_coordinator_merge(agg, scan)

    # ------------------------------------------------------------------
    # cross-host repartitioned join (the DCN analog of parallel/dist.py's
    # FIXED_HASH joins: optimizations/AddExchanges.java:738 choosing a
    # partitioned distribution + PartitionedOutputBuffer feeding the
    # consumer stage's ExchangeOperator)
    # ------------------------------------------------------------------
    def _partitionable_join(self, chain: PlanNode):
        """Outermost join on the probe spine that the distribution
        decision repartitions and whose both sides are scan-rooted
        chains with plain column keys (partitioning needs key channel
        indices and per-worker split assignment on each side)."""
        from presto_tpu.expr.ir import ColumnRef
        from presto_tpu.parallel.fragment import decide_join_distribution
        from presto_tpu.planner.plan import CrossSingleNode, JoinNode

        node = chain
        while True:
            if isinstance(node, (FilterNode, ProjectNode)):
                node = node.source
            elif isinstance(node, AggregationNode) and node.step == "partial":
                node = node.source
            elif isinstance(node, CrossSingleNode):
                node = node.left
            elif isinstance(node, JoinNode):
                if node.kind in ("full",) or node.use_index:
                    node = node.left
                    continue
                mode, _ = decide_join_distribution(
                    node, self.broadcast_threshold, catalog=self.catalog)
                ok = (
                    mode == "partitioned"
                    and all(isinstance(e, ColumnRef) for e in node.left_keys)
                    and all(isinstance(e, ColumnRef) for e in node.right_keys)
                    and isinstance(chain_leaf(node.left),
                                   TableScanNode)
                    and isinstance(chain_leaf(node.right),
                                   TableScanNode)
                )
                if ok:
                    return node
                node = node.left
            else:
                return None

    def _await_finished(self, tasks: List[tuple],
                        timeout: float = 120.0) -> None:
        """Poll task status until every task leaves RUNNING (the phased
        gate between build and probe stages).  Bounded: on timeout the
        next phase launches anyway — the pull buffers' backpressure
        keeps a still-running build correct, just un-phased."""
        deadline = time.monotonic() + timeout
        for w, tid in tasks:
            while time.monotonic() < deadline:
                try:
                    req = urllib.request.Request(f"{w.uri}/v1/task/{tid}")
                    with urllib.request.urlopen(req, timeout=10.0) as resp:
                        state = json.load(resp).get("state")
                except Exception:
                    return  # worker fault: surfaced by the next pull
                if state != "RUNNING":
                    break
                time.sleep(0.02)

    def _fan_out_stage2(self, alive: List["WorkerClient"], make_frag,
                        stage2: List[tuple]) -> List[bytes]:
        """Create + drain one stage-2 task per worker concurrently
        (make_frag(k) -> fragment json for worker k; created tasks are
        appended to ``stage2`` for caller cleanup).  Error triage is
        shared by every shuffle tier: GroupCapacityExceeded anywhere ->
        _StageCapacity (caller doubles and re-runs); transport faults ->
        ConnectionError (caller falls back to a degraded path);
        deterministic task errors -> TaskFailed."""
        results: List[bytes] = []
        errors: List[Exception] = []
        lock = named_lock("multihost._fan_out_stage2.lock")
        # timeline captured on the scheduling thread: run_one executes
        # on mh-stage2-* threads, which never inherit the recording TLS
        from presto_tpu.obs import current_timeline

        tl = current_timeline()

        def run_one(w: WorkerClient, k: int):
            t0 = time.perf_counter()
            try:
                tid = w.create_task(make_frag(k))
                with lock:
                    stage2.append((w, tid))
                raws = w.pull_results(tid)
                with lock:
                    results.extend(raws)
                if tl is not None:
                    tl.extend("fragment_ms", w.uri,
                              (time.perf_counter() - t0) * 1e3)
            except Exception as e:
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=run_one, args=(w, k),
                                    daemon=True, name=f"mh-stage2-{k}")
                   for k, w in enumerate(alive)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        if errors:
            msg = " ".join(str(e) for e in errors)
            if "GroupCapacityExceeded" in msg:
                raise _StageCapacity(msg)
            # a worker dying mid-shuffle surfaces as transport errors
            # INSIDE a task's error text (a stage-2 pull hit
            # connection-refused); that is a cluster fault, not a
            # deterministic query failure
            if any(t in msg for t in _TRANSPORT_MARKERS):
                raise ConnectionError(msg)
            for e in errors:
                if isinstance(e, TaskFailed):
                    raise e
            raise ConnectionError(msg)
        return results

    def _launch_stage1(self, subtree: PlanNode, scan: TableScanNode,
                       key_indices: List[int], key_domains,
                       alive: List["WorkerClient"]) -> List[tuple]:
        """Run ``subtree`` on every worker over disjoint split subsets,
        each task hash-partitioning its output rows on ``key_indices``
        into one buffer per worker.  ``key_domains`` must be the JOIN's
        union domains so both sides pack (and therefore route)
        identically."""
        K = len(alive)
        spec = {
            "partitions": K,
            "key_indices": list(key_indices),
            "domains": [list(d) if d is not None else None
                        for d in key_domains],
        }
        n_splits = scan.handle.num_splits
        split_sets = [list(range(n_splits))[i::K] for i in range(K)]
        tasks: List[tuple] = []
        for w, splits in zip(alive, split_sets):
            original = scan.splits
            try:
                scan.splits = splits
                frag = plan_to_json(subtree)
            finally:
                scan.splits = original
            tasks.append((w, w.create_task(frag, spec)))
        return tasks

    def _run_agg_partitioned_join(self, agg: AggregationNode, join,
                                  alive: List["WorkerClient"]):
        """Shuffle join over DCN: stage 1 scans each side and
        hash-partitions rows on the join key into K buffers; stage-2
        worker k pulls partition k of BOTH sides from every stage-1
        task, builds the join over its build shard, probes, and runs the
        partial aggregation; the coordinator merges the K partial
        outputs."""
        import numpy as np

        from presto_tpu.exec.local import (
            MAX_AGG_GROUPS,
            GroupCapacityExceeded,
        )
        from presto_tpu.planner.plan import RemoteSourceNode

        K = len(alive)
        kd = join.key_domains
        lidx = [e.index for e in join.left_keys]
        ridx = [e.index for e in join.right_keys]
        probe_scan = chain_leaf(join.left)
        build_scan = chain_leaf(join.right)
        mg = self.local._max_groups(agg)

        while True:
            # re-derive per retry: once mg covers the exact key-domain
            # product, a full partial page means completeness, not
            # overflow (stale check caused needless two-sided rescans)
            check = bool(agg.group_exprs) and not self.local._exact_capacity(
                agg, mg)
            stage1: List[tuple] = []
            stage2: List[tuple] = []
            try:
                # stage launch order comes from the schedule policy
                # (scheduler.py): phased gates the probe side on the
                # build side's tasks FINISHING (builds are fully
                # buffered before probes start scanning — the
                # PhasedExecutionSchedule.java property); all_at_once
                # launches both sides immediately
                from presto_tpu.parallel.scheduler import (
                    AllAtOnceExecutionSchedule,
                    PhasedExecutionSchedule,
                )

                class _Side:
                    def __init__(self, name, args, children=()):
                        self.name = name
                        self.args = args
                        self.children = list(children)

                build_side = _Side(
                    "build", (join.right, build_scan, ridx))
                probe_side = _Side(
                    "probe", (join.left, probe_scan, lidx), [build_side])
                sched_cls = (PhasedExecutionSchedule
                             if self.execution_policy == "phased"
                             else AllAtOnceExecutionSchedule)
                launched: Dict[str, List[tuple]] = {}
                phases = sched_cls([probe_side]).phases()
                for pi, phase in enumerate(phases):
                    for side in phase:
                        subtree, scan_, idx_ = side.args
                        tasks = self._launch_stage1(
                            subtree, scan_, idx_, kd, alive)
                        launched[side.name] = tasks
                        stage1 += tasks
                    if pi + 1 < len(phases):
                        self._await_finished(launched["build"])
                build_tasks = launched["build"]
                probe_tasks = launched["probe"]

                partial = AggregationNode(
                    source=agg.source, group_exprs=agg.group_exprs,
                    group_names=agg.group_names, aggs=agg.aggs,
                    agg_names=agg.agg_names, step="partial", max_groups=mg,
                )
                orig_left, orig_right = join.left, join.right
                try:
                    join.left = RemoteSourceNode(
                        producer=orig_left,
                        tasks=[(w.uri, t) for w, t in probe_tasks])
                    join.right = RemoteSourceNode(
                        producer=orig_right,
                        tasks=[(w.uri, t) for w, t in build_tasks])
                    frag_base = plan_to_json(partial)
                finally:
                    join.left, join.right = orig_left, orig_right

                def make_frag(k: int) -> dict:
                    frag = json.loads(json.dumps(frag_base))
                    _set_remote_buffers(frag, k)
                    return frag

                try:
                    results = self._fan_out_stage2(alive, make_frag, stage2)
                except _StageCapacity:
                    if mg >= MAX_AGG_GROUPS:
                        raise RuntimeError(
                            f"distributed aggregation exceeded "
                            f"{MAX_AGG_GROUPS} groups")
                    mg *= 2
                    continue

                dicts = [c.dictionary for c in partial.channels]
                pages = [deserialize_page(r, dicts, verify=False) for r in results]
                if not pages:
                    from presto_tpu.page import Page

                    pages = [Page.empty(
                        [c.type for c in partial.channels], 1)]
                if check and any(
                    int(np.asarray(p.row_mask).sum()) >= mg for p in pages
                ):
                    if mg >= MAX_AGG_GROUPS:
                        raise RuntimeError("aggregation capacity ceiling")
                    mg *= 2
                    continue

                # group keys were hash-partitioned on the JOIN key, not
                # the group key, so partitions may share groups: finish
                # with the coordinator merge (cheap — inputs are K
                # partial states)
                merge_mg = mg
                while True:
                    final = AggregationNode(
                        source=PrecomputedNode(
                            page=concat_pages_device(pages),
                            channel_list=partial.channels,
                        ),
                        group_exprs=[_key_ref(partial, i)
                                     for i in range(len(agg.group_exprs))],
                        group_names=agg.group_names, aggs=agg.aggs,
                        agg_names=agg.agg_names, step="final",
                        max_groups=merge_mg,
                    )
                    try:
                        return self.local._execute_to_page(final)
                    except GroupCapacityExceeded:
                        if merge_mg >= MAX_AGG_GROUPS:
                            raise RuntimeError(
                                "aggregation capacity ceiling")
                        merge_mg *= 2
            finally:
                for w, tid in stage1 + stage2:
                    w.delete_task(tid)

    def _run_agg_two_stage(self, agg: AggregationNode, scan: TableScanNode,
                           alive: List[WorkerClient]):
        """Worker-to-worker partitioned exchange: stage-1 tasks produce
        hash-partitioned partial-aggregation pages into K per-partition
        buffers; stage-2 task k (on worker k) pulls partition k from
        EVERY stage-1 task via a RemoteSource leaf and finishes the
        aggregation there.  The coordinator drains only stage-2 outputs
        — traffic proportional to the RESULT, not the data (reference:
        PartitionedOutputBuffer.java + ExchangeOperator.java:36;
        previously the coordinator merged every partial state itself,
        the scalability ceiling VERDICT r2 flagged)."""
        import numpy as np

        from presto_tpu.exec.local import MAX_AGG_GROUPS
        from presto_tpu.planner.plan import RemoteSourceNode

        K = len(alive)
        num_keys = len(agg.group_exprs)
        mg = self.local._max_groups(agg)

        n_splits = scan.handle.num_splits
        split_sets = [list(range(n_splits))[i::K] for i in range(K)]

        while True:
            partial = AggregationNode(
                source=agg.source, group_exprs=agg.group_exprs,
                group_names=agg.group_names, aggs=agg.aggs,
                agg_names=agg.agg_names, step="partial", max_groups=mg,
            )
            pch = partial.channels
            output_spec = {
                "partitions": K,
                "key_indices": list(range(num_keys)),
                "domains": [list(d) if d is not None else None
                            for d in (pch[i].domain for i in range(num_keys))],
            }

            stage1: List[tuple] = []  # (worker, task_id)
            stage2: List[tuple] = []
            try:
                for w, splits in zip(alive, split_sets):
                    original = scan.splits
                    try:
                        scan.splits = splits
                        frag = plan_to_json(partial)
                    finally:
                        scan.splits = original
                    stage1.append((w, w.create_task(frag, output_spec)))

                upstream = [(w.uri, tid) for w, tid in stage1]
                final = AggregationNode(
                    source=RemoteSourceNode(producer=partial, tasks=upstream,
                                            buffer_id=0),
                    group_exprs=[_key_ref(partial, i) for i in range(num_keys)],
                    group_names=agg.group_names, aggs=agg.aggs,
                    agg_names=agg.agg_names, step="final", max_groups=mg,
                )
                fin_base = plan_to_json(final)

                def make_frag(k: int) -> dict:
                    fin = json.loads(json.dumps(fin_base))
                    fin["src"]["buffer"] = k
                    return fin

                try:
                    results = self._fan_out_stage2(alive, make_frag, stage2)
                except _StageCapacity:
                    if mg >= MAX_AGG_GROUPS:
                        raise RuntimeError(
                            f"distributed aggregation exceeded "
                            f"{MAX_AGG_GROUPS} groups")
                    mg *= 2
                    continue

                dicts = [c.dictionary for c in final.channels]
                pages = [deserialize_page(r, dicts, verify=False) for r in results]
                if not pages:
                    from presto_tpu.page import Page

                    return Page.empty(final.output_types, 1)
                # stage-2 outputs are disjoint partitions: concatenation
                # IS the final result (no re-merge needed)
                merged = concat_pages_device(pages)
                # defensive: a stage-2 task at full capacity may have
                # truncated (its own _check_overflow raises before this,
                # but verify the invariant cheaply) — except for
                # exact-capacity aggs, where a full page is completeness
                if not self.local._exact_capacity(agg, mg) and any(
                    int(np.asarray(p.row_mask).sum()) >= mg for p in pages
                ):
                    if mg >= MAX_AGG_GROUPS:
                        raise RuntimeError("aggregation capacity ceiling")
                    mg *= 2
                    continue
                return merged
            finally:
                for w, tid in stage1 + stage2:
                    w.delete_task(tid)

    def _run_agg_coordinator_merge(self, agg: AggregationNode, scan: TableScanNode):
        """Worker partial aggs truncate silently at max_groups (static
        shapes), so the coordinator checks every returned partial page's
        live-row count and the final merge's capacity, retrying the
        whole stage with doubled max_groups — the DCN counterpart of
        LocalRunner._check_overflow."""
        import numpy as np

        from presto_tpu.exec.local import MAX_AGG_GROUPS, GroupCapacityExceeded

        def grow(mg: int) -> int:
            if mg >= MAX_AGG_GROUPS:
                raise RuntimeError(
                    f"distributed aggregation exceeded {MAX_AGG_GROUPS} groups"
                )
            return mg * 2

        mg = self.local._max_groups(agg)
        check = bool(agg.group_exprs) and not self.local._exact_capacity(agg, mg)
        while True:
            partial = AggregationNode(
                source=agg.source, group_exprs=agg.group_exprs,
                group_names=agg.group_names, aggs=agg.aggs, agg_names=agg.agg_names,
                step="partial", max_groups=mg,
            )
            partial_pages = self._run_fragments(partial, scan)
            if check and any(
                int(np.asarray(p.row_mask).sum()) >= mg for p in partial_pages
            ):
                mg = grow(mg)
                continue

            # partial pages stay valid at any larger merge capacity, so
            # a final-merge overflow only re-runs the (cheap) merge —
            # not the distributed scan fragments
            merge_mg = mg
            while True:
                final = AggregationNode(
                    source=PrecomputedNode(
                        page=concat_pages_device(partial_pages),
                        channel_list=partial.channels,
                    ),
                    group_exprs=[
                        _key_ref(partial, i) for i in range(len(agg.group_exprs))
                    ],
                    group_names=agg.group_names, aggs=agg.aggs,
                    agg_names=agg.agg_names, step="final", max_groups=merge_mg,
                )
                try:
                    return self.local._execute_to_page(final)
                except GroupCapacityExceeded:
                    merge_mg = grow(merge_mg)

    def _leaf_scan(self, node: PlanNode) -> TableScanNode:
        n = chain_leaf(node)
        if not isinstance(n, TableScanNode):
            raise MultiHostUnsupported("chain leaf is not a table scan")
        return n

    # ------------------------------------------------------------------
    def _run_fragments(self, fragment_root: PlanNode, scan: TableScanNode):
        """Schedule split ranges across live workers; reassign a failed
        worker's splits to survivors (elastic leaf recovery) under a
        bounded per-stage retry budget, and finish remaining splits
        with coordinator-local execution when no worker can run them.
        The shipped fragment is ``fragment_root``'s subtree with the
        scan's split list swapped per assignment."""
        alive = self._live_workers()
        if not alive:
            raise MultiHostUnsupported("no live workers")

        from presto_tpu.parallel.scheduler import NodeSelector

        conn = self.catalog.connector(scan.handle.connector_name)
        n_splits = scan.handle.num_splits
        # live progress: the DCN fan-out is the long pole of a
        # multi-host query — publish splits-done/total as worker tasks
        # land (the stage scheduler's completedDrivers analog)
        from presto_tpu.obs import current_progress

        prog = current_progress()
        prog_stage = None
        if prog is not None:
            prog_stage = prog.new_stage_name(
                f"mh:{scan.handle.table}")
            prog.stage(prog_stage, splits_total=n_splits)
        preferred = None
        if hasattr(conn, "split_location"):
            preferred = {s: conn.split_location(scan.handle.table, s)
                         for s in range(n_splits)}
        selector = NodeSelector(
            alive, max_splits_per_node=self.max_splits_per_node,
            locations={id(w): self.worker_locations.get(w)
                       for w in alive})
        assignments: Dict[WorkerClient, List[int]] = selector.assign(
            range(n_splits), preferred)
        self.last_assignments = {w.uri: list(s)
                                 for w, s in assignments.items()}

        results: List[bytes] = []
        lock = named_lock("multihost._run_fragments.lock")
        failed: List[tuple] = []

        dictionaries = [c.dictionary for c in fragment_root.channels]

        def make_fragment(splits: List[int]) -> dict:
            # serialize on the scheduling thread — the splits field is
            # set transiently on the shared scan node
            original = scan.splits
            try:
                scan.splits = splits
                return plan_to_json(fragment_root)
            finally:
                scan.splits = original

        if self.exchange_streaming:
            pages = self._stream_fragment_pairs(
                fragment_root, list(assignments.items()), make_fragment,
                run_local=lambda splits, skip: self._run_splits_local(
                    fragment_root, scan, splits)[skip:],
                prog=prog, prog_stage=prog_stage, prog_n=len)
            if prog is not None:
                prog.finish_stage(prog_stage)
            return pages

        errors: List[BaseException] = []
        # timeline captured on the scheduling thread: run_on executes on
        # mh-fragment-* threads, which never inherit the recording TLS
        from presto_tpu.obs import current_timeline

        tl = current_timeline()

        def run_on(w: WorkerClient, splits: List[int], fragment: dict):
            t0 = time.perf_counter()
            try:
                raws = w.run_fragment(fragment)
                with lock:
                    results.extend(raws)
                if tl is not None:
                    # per-worker wall time: the doctor's straggler
                    # evidence (fragment_ms keyed by worker uri)
                    tl.extend("fragment_ms", w.uri,
                              (time.perf_counter() - t0) * 1e3)
                if prog is not None:
                    prog.split_done(prog_stage, n=len(splits),
                                    nbytes=sum(len(r) for r in raws))
            except ConnectionError:
                with lock:
                    failed.append((w, splits))
            except BaseException as e:  # deterministic query error:
                with lock:              # fail the query rather than
                    errors.append(e)    # silently dropping the splits

        def launch(pairs):
            threads = [
                threading.Thread(target=run_on, args=(w, s,
                                                      make_fragment(s)),
                                 daemon=True, name=f"mh-fragment-{i}")
                for i, (w, s) in enumerate(pairs) if s
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        launch(assignments.items())

        # failover: re-run dead workers' splits on survivors (striped
        # across all of them), spending the bounded per-stage retry
        # budget; when the budget is gone or no worker survives, the
        # coordinator runs the remaining splits itself (fragments are
        # pure — local execution is the always-correct last resort,
        # used ONLY when no worker can)
        def redispatch(item, survivors, _rr):
            _w_dead, splits = item
            chunks = [splits[i :: len(survivors)]
                      for i in range(len(survivors))]
            launch(list(zip(survivors, chunks)))

        def run_local(item):
            _w_dead, splits = item
            pages = self._run_splits_local(fragment_root, scan, splits)
            if prog is not None:
                prog.split_done(prog_stage, n=len(splits))
            return pages

        local_pages = self._failover(failed, alive, errors,
                                     redispatch, run_local)

        if prog is not None:
            prog.finish_stage(prog_stage)
        return [deserialize_page(r, dictionaries, verify=False)
                for r in results] + local_pages


    # -- streaming fragment fan-out ------------------------------------
    def _pull_fragment_pages(self, w: "WorkerClient", fragment: dict, emit,
                             dicts, skip: int = 0) -> int:
        """Create + drain one fragment task, emitting each verified,
        deserialized page as it lands in the worker's output buffer
        (``emit(page, nbytes)``) — the streaming twin of
        WorkerClient.run_fragment, with the same transient/deterministic
        triage.  ``skip`` pages are discarded first: the consumer
        already took them from a previous incarnation of this fragment
        (replay from the last acked token; fragments are pure and page
        order deterministic, so the re-run's prefix is byte-equal).
        Returns the delivered-page watermark; raises _StreamBroken
        (carrying it) when the worker dies mid-stream, TaskFailed on a
        deterministic query error."""
        from presto_tpu.net import is_transient
        from presto_tpu.obs import METRICS
        from presto_tpu.server.serde import deserialize_page, verify_page
        from presto_tpu.server.shuffle_client import (
            TaskPullFailed, pull_pages,
        )

        delivered = skip
        last: Optional[BaseException] = None
        if RECORDER.enabled and self.detector is not None:
            self.detector.note_assignment(w.uri)
        for attempt in range(w.max_attempts):
            if delivered > 0 and (attempt > 0 or skip > 0):
                # this task re-produces pages the consumer already has
                METRICS.counter("exchange.stream_replays_total").inc()
            tid = None
            skip_target = delivered  # prefix this incarnation replays
            skipped = 0
            try:
                tid = w.create_task(fragment)
                for raw in pull_pages(w.uri, tid, 0, timeout=w.timeout):
                    if skipped < skip_target:
                        skipped += 1
                        continue
                    verify_page(raw)
                    emit(deserialize_page(raw, dicts, verify=False),
                         len(raw))
                    delivered += 1
                w._ok()
                return delivered
            except TaskPullFailed as e:
                if "PageIntegrityError" not in str(e):
                    # deterministic query error: it travels; the worker
                    # is not to blame and a retry recomputes the same
                    raise TaskFailed(str(e)) from e
                last = e  # damaged in-fragment input page: recompute
            except TaskFailed:
                raise
            except Exception as e:
                if not is_transient(e):
                    raise TaskFailed(f"{type(e).__name__}: {e}") from e
                last = e
            finally:
                if tid is not None:
                    w.delete_task(tid)
            time.sleep(min(0.1 * (2 ** attempt), 2.0))
        w._failed(last)
        raise _StreamBroken(delivered, last)

    def _stream_fragment_pairs(self, fragment_root: PlanNode, pairs,
                               make_fragment, run_local,
                               prog=None, prog_stage=None,
                               prog_n=lambda item: 1) -> List["Page"]:
        """Streaming fan-out driver shared by the scan-leaf and
        pre-chunk fragment paths: one puller thread per (worker, item)
        feeds a token-acked PageStream and the consumer takes pages the
        moment the FIRST producer emits — stage k+1 overlaps stage k.
        Mid-stream producer death re-dispatches the SAME fragment onto
        a survivor with the delivered-page watermark (replay), under
        the usual bounded retry budget, finishing coordinator-local
        (``run_local(item, skip)``) when no worker can.

        Pages travel tagged (producer slot, sequence) and the returned
        list is reassembled in assignment order — byte-identical to the
        materialized gather — so order-carrying inputs (a chain stage
        over a sorted intermediate) survive arrival-order races; the
        overlap (pull + verify + deserialize while producers still run)
        is unaffected."""
        from presto_tpu.parallel.streams import PageStream

        dicts = [c.dictionary for c in fragment_root.channels]
        live = [(slot, w, item, make_fragment(item))
                for slot, (w, item) in enumerate(p for p in pairs if p[1])]
        stream = PageStream(max_bytes=self.exchange_buffer_bytes,
                            producers=max(len(live), 1), name="mh:gather")
        slotted: List[tuple] = []  # (slot, seq, page)
        failed: List[tuple] = []
        errors: List[BaseException] = []
        lock = named_lock("multihost._stream_fragment_pairs.lock")

        def emit_into(put, slot: int, start: int = 0):
            seq = [start]
            pk = f"mh:{id(stream):x}:{slot}"

            def emit(page, nbytes):
                if RECORDER.enabled:
                    # per-slot canonical sequencing: the spec automaton
                    # checks exactly-once delivery + replay-prefix
                    # equality across fragment re-incarnations
                    RECORDER.record("exchange", pk, "deliver", seq=seq[0])
                put((slot, seq[0], page), nbytes=nbytes)
                seq[0] += 1

            return emit

        # timeline captured on the consumer thread: run_on executes on
        # mh-stream-pull-* threads, which never inherit the recording TLS
        from presto_tpu.obs import current_timeline

        tl = current_timeline()

        def run_on(slot: int, w: WorkerClient, item, fragment: dict):
            t0 = time.perf_counter()
            try:
                self._pull_fragment_pages(
                    w, fragment, emit_into(stream.put, slot), dicts)
                if tl is not None:
                    tl.extend("fragment_ms", w.uri,
                              (time.perf_counter() - t0) * 1e3)
                if prog is not None:
                    prog.split_done(prog_stage, n=prog_n(item))
            except _StreamBroken as e:
                with lock:
                    failed.append((slot, item, fragment, e.delivered))
            except ConnectionError:
                with lock:
                    failed.append((slot, item, fragment, 0))
            except BaseException as e:  # deterministic query error:
                with lock:              # fail the query rather than
                    errors.append(e)    # silently dropping the rows
            finally:
                stream.producer_done()

        if not live:
            stream.producer_done()
        threads = [threading.Thread(target=run_on, args=t, daemon=True,
                                    name=f"mh-stream-pull-{t[0]}")
                   for t in live]
        for t in threads:
            t.start()
        try:
            for tagged in stream.drain():
                slotted.append(tagged)
        finally:
            # join in a finally (sanitizer thread-leak): a consumer-side
            # error (kill/abort raising out of drain) must still reap
            # the pullers — drain's early-close abort has already
            # unblocked any producer stuck on the byte cap
            for t in threads:
                t.join(timeout=30.0)
        self.last_exchange_stats = {
            "pages": float(stream.pages_in),
            "bytes": float(stream.bytes_in),
            "peak_buffered_bytes": float(stream.peak_bytes),
            "first_page_at": stream.first_page_at or 0.0,
            "producers_done_at": stream.completed_at or 0.0,
        }

        def redispatch(item4, survivors, rr):
            slot, item, fragment, delivered = item4
            w = survivors[rr % len(survivors)]
            if RECORDER.enabled:
                # skip must equal the consumer's delivered watermark —
                # the automaton cross-checks it against its own count
                RECORDER.record("exchange", f"mh:{id(stream):x}:{slot}",
                                "replay", skip=delivered)
            emit = emit_into(
                lambda tagged, nbytes: slotted.append(tagged), slot,
                start=delivered)
            try:
                self._pull_fragment_pages(w, fragment, emit, dicts,
                                          skip=delivered)
                if prog is not None:
                    prog.split_done(prog_stage, n=prog_n(item))
            except _StreamBroken as e:
                with lock:
                    failed.append((slot, item, fragment, e.delivered))
            except ConnectionError:
                with lock:
                    failed.append((slot, item, fragment, delivered))
            except BaseException as e:
                with lock:
                    errors.append(e)

        def run_local_item(item4):
            slot, item, _fragment, delivered = item4
            out = run_local(item, delivered)
            if prog is not None:
                prog.split_done(prog_stage, n=prog_n(item))
            if RECORDER.enabled:
                pk = f"mh:{id(stream):x}:{slot}"
                RECORDER.record("exchange", pk, "replay", skip=delivered)
                for i in range(len(out)):
                    RECORDER.record("exchange", pk, "deliver",
                                    seq=delivered + i)
            return [(slot, delivered + i, p) for i, p in enumerate(out)]

        slotted.extend(self._failover(
            failed, [w for _, w, _, _ in live], errors, redispatch,
            run_local_item))
        slotted.sort(key=lambda t: (t[0], t[1]))
        return [p for _, _, p in slotted]

    # -- shared failover driver ----------------------------------------
    def _failover(self, failed: List, alive: List["WorkerClient"],
                  errors: List[BaseException], redispatch, run_local):
        """Drain the ``failed`` work list: re-dispatch each item onto
        survivors under the bounded per-stage retry budget
        (``redispatch(item, survivors, attempt_index)``), falling back
        to coordinator-local execution (``run_local(item)`` -> pages)
        when no worker survives or the budget is spent.  Raises the
        first deterministic error instead of dropping rows; returns
        the locally recovered pages."""
        from presto_tpu.obs import METRICS

        local_pages: List = []
        budget = self.max_fragment_retries
        pkey = None
        if RECORDER.enabled:
            pkey = f"fo:{id(self):x}:{next(_FAILOVER_SEQ)}"
            RECORDER.record("retry", pkey, "begin", budget=budget)
        rr = 0
        while failed:
            if errors:
                break
            item = failed.pop()
            survivors = [w for w in alive if w.alive]
            if not survivors or budget <= 0:
                if pkey is not None:
                    RECORDER.record("retry", pkey, "local",
                                    survivors=len(survivors),
                                    budget_left=max(budget, 0))
                local_pages.extend(run_local(item))
                continue
            budget -= 1
            METRICS.counter("retry.fragments_total").inc()
            if pkey is not None:
                RECORDER.record("retry", pkey, "retry",
                                used=self.max_fragment_retries - budget)
            redispatch(item, survivors, rr)
            rr += 1
        if errors:
            raise errors[0]
        return local_pages

    # -- coordinator-local last resort ---------------------------------
    def _local_fragment_pages(self, fragment_root: PlanNode):
        """Run a fragment on the coordinator's own LocalRunner, round-
        tripping the wire serde so downstream merging sees exactly
        what a worker would have shipped."""
        from presto_tpu.server.serde import serialize_page

        raws = [serialize_page(p)
                for p in self.local._pages(fragment_root)]
        dicts = [c.dictionary for c in fragment_root.channels]
        return [deserialize_page(r, dicts, verify=False) for r in raws]

    def _run_splits_local(self, fragment_root: PlanNode,
                          scan: TableScanNode, splits: List[int]):
        """Execute a scan-leaf fragment's splits on the coordinator —
        the terminal fallback when every worker is dead or the retry
        budget is spent."""
        from presto_tpu.obs import METRICS

        METRICS.counter("retry.splits_recovered_local").inc(len(splits))
        _log.warning(
            "no worker available for %d split(s) of %s; finishing them "
            "on the coordinator", len(splits), scan.handle.table)
        original = scan.splits
        try:
            scan.splits = list(splits)
            return self._local_fragment_pages(fragment_root)
        finally:
            scan.splits = original

    def _run_chunk_local(self, fragment_root: PlanNode,
                         pre: PrecomputedNode, chunk):
        """_run_splits_local for a materialized-intermediate chunk."""
        from presto_tpu.obs import METRICS

        METRICS.counter("retry.splits_recovered_local").inc()
        _log.warning("no worker available for an intermediate chunk; "
                     "finishing it on the coordinator")
        original = pre.page
        try:
            pre.page = chunk
            return self._local_fragment_pages(fragment_root)
        finally:
            pre.page = original


def _chunk_page(page, k: int):
    """Row-chunk a (possibly device) page into ``k`` contiguous
    host-side pieces for re-distribution; dead rows are dropped first
    so chunk sizes reflect live data."""
    from presto_tpu.page import Block, Page

    p = page.compact_host()
    n = int(np.asarray(p.row_mask).sum())
    bounds = [round(i * n / k) for i in range(k + 1)]
    chunks = []
    for lo, hi in zip(bounds, bounds[1:]):
        if hi == lo:
            chunks.append(None)
            continue
        blocks = tuple(
            Block(b.data[lo:hi], b.valid[lo:hi], b.type, b.dictionary)
            for b in p.blocks)
        chunks.append(Page(blocks, p.row_mask[lo:hi]))
    return chunks


def _key_ref(partial: AggregationNode, i: int):
    from presto_tpu.expr.ir import ColumnRef

    ch = partial.channels[i]
    return ColumnRef(type=ch.type, index=i)


def _set_remote_buffers(frag_json: dict, k: int) -> None:
    """Point every RemoteSource leaf in a serialized fragment at
    partition buffer ``k`` (stage-2 task k consumes partition k of
    every upstream side)."""
    if isinstance(frag_json, dict):
        if frag_json.get("k") == "remote":
            frag_json["buffer"] = k
        for v in frag_json.values():
            _set_remote_buffers(v, k)
    elif isinstance(frag_json, list):
        for v in frag_json:
            _set_remote_buffers(v, k)
