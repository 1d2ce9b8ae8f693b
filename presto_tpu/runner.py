"""Top-level query runner: SQL in, rows out.

Reference analog: ``testing/LocalQueryRunner.java:207`` — the
full-pipeline in-process harness (parse -> analyze -> plan -> execute)
used by the reference's tests and benchmarks, and the model for the
coordinator's query lifecycle (execution/SqlQueryExecution.java).
Statement dispatch mirrors the coordinator's non-query statement
handlers (EXPLAIN via QueryExplainer, SET SESSION, SHOW metadata).
"""

from __future__ import annotations

from typing import Optional

from presto_tpu.catalog import Catalog
from presto_tpu.exec.local import (
    LocalRunner, MaterializedResult, QueryStats, arith_counts, chain_probes,
    compact_counts, expand_counts, host_reads,
)
from presto_tpu.session import Session
from presto_tpu.sql import ast
from presto_tpu.sql.binder import Binder
from presto_tpu.sql.parser import parse_statement
from presto_tpu.types import BIGINT, VARCHAR, Type


def _substitute_params(node, params):
    """Replace ? Parameter nodes with the EXECUTE ... USING expressions
    (sql/tree/Parameter.java rewriting in the reference's
    ParameterRewriter)."""
    import dataclasses as _dc

    if isinstance(node, ast.Parameter):
        if node.index >= len(params):
            raise ValueError(
                f"parameter ?{node.index + 1} has no USING value")
        return params[node.index]
    if isinstance(node, tuple):
        # nested tuples (With.ctes pairs, Case.whens) recurse
        return tuple(_substitute_params(x, params) for x in node)
    if not isinstance(node, ast.Node):
        return node
    changes = {}
    for f in _dc.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, (tuple, ast.Node)):
            nv = _substitute_params(v, params)
            if nv is not v and nv != v:
                changes[f.name] = nv
            elif isinstance(nv, tuple) and any(
                a is not b for a, b in zip(nv, v)
            ):
                changes[f.name] = nv
    return _dc.replace(node, **changes) if changes else node


def _count_parameters(node) -> int:
    """Number of ? placeholders in a statement tree."""
    import dataclasses as _dc

    if isinstance(node, ast.Parameter):
        return 1
    if isinstance(node, tuple):
        return sum(_count_parameters(x) for x in node)
    if not isinstance(node, ast.Node):
        return 0
    return sum(_count_parameters(getattr(node, f.name))
               for f in _dc.fields(node))


class QueryRunner:
    def __init__(self, catalog: Catalog, session: Optional[Session] = None, jit: bool = True,
                 memory_pool=None, access_control=None, programs=None):
        from presto_tpu.events import EventListenerManager
        from presto_tpu.security import AccessControl

        self.catalog = catalog
        self.session = session or Session()
        # program registry shared by every executor this runner builds
        # (SET SESSION rebuilds the executor; compiled programs survive)
        self.programs = programs
        self.binder = Binder(catalog, session=self.session)
        self._jit_default = jit
        # Accounting is always-on (memory/MemoryPool.java:43 tracks
        # every operator unconditionally): None selects the process
        # pool sized to detected HBM/RAM; False disables (tests only).
        if memory_pool is None:
            from presto_tpu.memory import default_memory_pool

            memory_pool = default_memory_pool()
        self.memory_pool = memory_pool or None
        self.access_control = access_control or AccessControl()
        self.events = EventListenerManager()
        # per-session explicit transaction (transaction/TransactionManager.java)
        from presto_tpu.transaction import TransactionManager

        self.transactions = TransactionManager()
        self._open_tx = None
        # PREPARE name FROM <query> registry (StatementResource's
        # prepared-statement session map analog)
        self._prepared = {}
        # CALL registry (ProcedureRegistry.java); kill_query ships
        # built-in like the reference's KillQueryProcedure
        self.procedures = {
            "system.runtime.kill_query": self._kill_query_procedure,
        }
        self.executor = self._make_executor()
        # estimate-vs-actual: a warehouse-backed catalog persists its
        # plan history next to the metastore (obs/history.py); catalogs
        # without a warehouse share the process in-memory store
        try:
            from presto_tpu.obs.history import (
                ensure_default_history, history_path,
            )
            from presto_tpu.storage.warehouse import WarehouseConnector

            for _c in catalog._connectors.values():
                if isinstance(_c, WarehouseConnector):
                    ensure_default_history(history_path(_c.root))
                    break
        except Exception:
            pass  # history must never block runner construction
        # plan cache: repeated executions of the same SQL reuse the same
        # plan-node identities, so the executor's compiled-chain caches
        # hit and nothing retraces (ExpressionCompiler's cache role,
        # sql/gen/ExpressionCompiler.java:53 cache field)
        self._plans = {}

    def _make_executor(self) -> LocalRunner:
        cap = self.session.get("split_capacity") or None
        ex = LocalRunner(
            self.catalog,
            jit=self._jit_default and self.session.get("jit"),
            split_capacity=cap,
            memory_pool=self.memory_pool,
            programs=self.programs,
            # 0 / -1 = process default (config/env resolved once in
            # exec/tasks.py); any positive session value wins per query
            task_concurrency=int(self.session.get("task_concurrency")) or None,
            task_prefetch=int(self.session.get("task_prefetch")),
        )
        ex.merge_sort = bool(self.session.get("distributed_sort"))
        return ex

    # ------------------------------------------------------------------
    def plan(self, sql: str):
        plan = self._plans.get(sql)
        if plan is None:
            plan = self._validated(self.binder.plan(sql))
            self._plans[sql] = plan
        return plan

    def _validated(self, plan):
        """Run the static plan/IR validator when always-on checking is
        enabled (``validate_plans`` session property or the process-wide
        ``PRESTO_TPU_VALIDATE_PLANS`` switch the test harness sets);
        cached plans validate once at bind time.  The kernel-soundness
        tier (``validate_kernels`` / ``PRESTO_TPU_VALIDATE_KERNELS``)
        gates the same way: the abstract interpreter proves overflow,
        lossy-cast, division, accumulator, and null-policy soundness of
        every compiled expression before the plan can execute."""
        from presto_tpu.analysis import (kernel_validation_enabled,
                                         validation_enabled)

        if validation_enabled() or self.session.get("validate_plans"):
            from presto_tpu.analysis import assert_valid

            assert_valid(plan)
        if kernel_validation_enabled() or self.session.get("validate_kernels"):
            from presto_tpu.analysis import assert_kernel_sound

            assert_kernel_sound(plan)
        return plan

    def _tracing_enabled(self) -> bool:
        """Span tracing is on when the ``trace`` session property asks
        for it or a trace directory is configured (query.trace-dir /
        PRESTO_TPU_TRACE_DIR) — otherwise every span call is the no-op
        fast path (obs/trace.py)."""
        from presto_tpu import obs

        try:
            if self.session.get("trace"):
                return True
        except KeyError:
            pass
        return obs.trace_dir() is not None

    def execute(self, sql: str, query_id=None,
                trace_token: Optional[str] = None) -> MaterializedResult:
        import time

        from presto_tpu.events import (
            QueryCompletedEvent, QueryCreatedEvent, new_query_id,
        )

        t_q0 = time.perf_counter()
        stmt = parse_statement(sql)
        parse_s = time.perf_counter() - t_q0

        if isinstance(stmt, (ast.Query, ast.Union, ast.With, ast.SetOp)):
            from presto_tpu import obs
            from presto_tpu.events import new_trace_token

            qid = query_id or new_query_id()
            trace = (trace_token or self.session.trace_token
                     or new_trace_token())
            tracer = None
            if self._tracing_enabled():
                tracer = obs.register(obs.Tracer(qid, trace))
                tracer.add_complete("parse", "lifecycle", t_q0, parse_s)
            t0 = time.time()
            obs.METRICS.counter("query.started").inc()
            obs.TASKS.start(qid, "local", trace_token=trace)
            # live progress: always registered (the statement protocol,
            # CLI and UI read it) — publication is one thread-local
            # read per split when nothing else is active
            progress = obs.register_progress(obs.QueryProgress(qid))
            # resource timeline: admission may have created it already
            # (queue-depth points + queued/blocked annotations land
            # before execution starts); None when timelines are off
            timeline = obs.ensure_timeline(qid)
            self.events.query_created(
                QueryCreatedEvent(qid, sql, self.session.user, t0, trace_token=trace)
            )
            planning_s: Optional[float] = None
            cache_hit: Optional[bool] = None
            with obs.tracing(tracer), obs.publishing(progress), \
                    obs.recording(timeline):
                try:
                    t1 = time.perf_counter()
                    with obs.span("plan", cat="lifecycle"):
                        plan = self._plan_cached(sql, stmt)
                        self._check_access(plan)
                        # serving tier: (key, versions) captured AT PLAN
                        # TIME so a write racing this execution leaves
                        # the stored entry stale-by-version, never
                        # silently current (serving/cache.py)
                        prepared = self._result_cache_prepared(plan)
                    planning_s = time.perf_counter() - t1
                    t1 = time.perf_counter()
                    # estimate-vs-actual: per-operator actuals sink,
                    # opt-in (one device sync per page)
                    qstats = (QueryStats()
                              if self.session.get("collect_stats") else None)
                    reads0 = host_reads()
                    compact0 = compact_counts()
                    expand0 = expand_counts()
                    arith0 = arith_counts()
                    probes0 = chain_probes()
                    with obs.span("execute", cat="lifecycle"):
                        res = None
                        if prepared is not None:
                            res = self._result_cache_hit(plan, prepared)
                            cache_hit = res is not None
                        if res is None:
                            res = self._run_plan(plan, qid, stats=qstats)
                    execution_s = time.perf_counter() - t1
                except Exception as e:
                    obs.METRICS.counter("query.failed").inc()
                    progress.mark_done()
                    err = f"{type(e).__name__}: {e}"
                    obs.TASKS.finish(qid, "FAILED", error=err)
                    self._finalize_trace(tracer, t_q0)
                    self.events.query_completed(QueryCompletedEvent(
                        qid, sql, self.session.user, "FAILED", t0, time.time(),
                        error=err, trace_token=trace,
                        planning_ms=self._ms(planning_s),
                    ))
                    raise
            # populate the result cache AFTER the query succeeded (and
            # outside the failure path: a cache anomaly must never fail
            # an already-executed query).  The entry carries the
            # versions captured at plan time, so a write that raced the
            # execution leaves it stale-by-version.
            if prepared is not None and not cache_hit:
                from presto_tpu.serving.cache import default_result_cache

                default_result_cache().store(
                    prepared, res.names, res.types, res.rows)
            progress.mark_done()
            compile_ms = (round(tracer.total_s("xla_compile") * 1e3, 3)
                          if tracer is not None else None)
            obs.METRICS.counter("query.finished").inc()
            obs.METRICS.counter("query.planning_seconds_total").inc(planning_s)
            obs.METRICS.counter("query.execution_seconds_total").inc(execution_s)
            obs.METRICS.histogram("query.execution_ms").observe(execution_s * 1e3)
            obs.TASKS.finish(qid, "FINISHED", rows=len(res.rows))
            # split-scheduler footprint onto the task row (local tier
            # only: a mesh run's executor stats would be stale).  The
            # thread-local accumulator is read, not last_task_stats —
            # concurrent queries on one runner must not swap footprints
            ts = self.executor._task_stats.as_dict()
            if not cache_hit and not self.session.get("distributed") \
                    and ts.get("splits"):
                obs.TASKS.update_scheduler(
                    qid, ts["splits"], ts["concurrency"],
                    ts["stall_s"] * 1e3, ts["prefetch_hits"])
            # per-run outcome off the result object (not the shared
            # runner fields — concurrent queries would swap stats)
            dist_stages = getattr(res, "dist_stages", None)
            dist_fallback = getattr(res, "dist_fallback", None)
            # stage times ride the result for the statement protocol
            res.planning_ms = self._ms(planning_s)
            res.compile_ms = compile_ms
            res.execution_ms = self._ms(execution_s)
            # blocking device reads this query made on this thread
            # (exec/local.host_read); 0 for a result-cache hit
            res.host_reads = host_reads() - reads0
            # pages of chains that ran compacted, and of chains that
            # held more live rows than the plan's estimate left room
            # for and ran again whole (_chain_pages)
            res.compacted_pages, res.compact_fallback_pages = (
                n - n0 for n, n0 in zip(compact_counts(), compact0))
            # expanding probes that ran again at a larger capacity, and
            # the rows expanding probes emitted (_probe_with_retry)
            res.expand_retries, res.expanded_rows = (
                n - n0 for n, n0 in zip(expand_counts(), expand0))
            # guarded arithmetic sites and limb sums the statement's
            # chains compiled with / without their runtime guard
            # (Chain.arith_counts; the plan's intervals decide)
            res.arith_checked, res.arith_proven = (
                n - n0 for n, n0 in zip(arith_counts(), arith0))
            # probes the statement's chains run in a row over each of
            # their pages (Chain.probes, summed over the chains)
            res.chain_probes = chain_probes() - probes0
            # serving-tier surfaces: whether this result came from the
            # structural cache, and the executor's observed peak bytes
            # (the admission controller's projection source for the
            # next run of this statement)
            res.cache_hit = cache_hit
            res.query_id = qid  # embedded callers (CLI --doctor) key
            # the timeline/doctor registries off the result itself
            res.peak_bytes = (0 if cache_hit
                              else getattr(self.executor,
                                           "last_peak_bytes", 0))
            self._finalize_trace(tracer, t_q0)
            # post-query diagnosis (obs/doctor.py): ranked findings from
            # the rulebook over trace + timeline + progress; they ride
            # the result (statement protocol), the timeline (the
            # /v1/query/<id>/doctor endpoint) and the completion event
            # (query-log `findings` field)
            wall_ms = ((res.planning_ms or 0.0) + (res.execution_ms or 0.0))
            queued_ms = memory_blocked_ms = None
            # estimate-vs-actual attribution: the worst-node ratio is
            # annotated BEFORE the doctor runs (its `misestimate` rule
            # reads it), feeds the plan-history store, and rides the
            # result + completion event + query-log line
            worst = None
            if qstats is not None and not cache_hit:
                from presto_tpu.obs.history import (
                    default_history, operator_rows, worst_estimate,
                )

                est_map = getattr(plan, "_estimates", None)
                worst = worst_estimate(qstats, est_map)
                if timeline is not None:
                    if worst is not None:
                        timeline.annotate("worst_estimate", worst)
                    # per-operator detail rows for the web UI /
                    # /v1/query/<id>/operators endpoint
                    timeline.annotate(
                        "operators", operator_rows(qstats, est_map))
                default_history().record_query(qstats, est_map)
            res.worst_estimate = worst
            res.worst_estimate_ratio = worst["ratio"] if worst else None
            if timeline is not None:
                timeline.annotate("wall_ms", wall_ms)
                if dist_fallback:
                    timeline.annotate("dist_fallback", dist_fallback)
                queued_ms = timeline.annotation("queued_ms")
                memory_blocked_ms = timeline.annotation("memory_blocked_ms")
            findings = [f.as_dict() for f in obs.doctor.diagnose(
                qid, tracer=tracer, timeline=timeline, progress=progress,
                wall_ms=wall_ms, dist_fallback=dist_fallback)]
            if timeline is not None:
                timeline.annotate("findings", findings)
            res.findings = findings
            res.queued_ms = queued_ms
            res.memory_blocked_ms = memory_blocked_ms
            self.events.query_completed(QueryCompletedEvent(
                qid, sql, self.session.user, "FINISHED", t0, time.time(),
                rows=len(res.rows), trace_token=trace,
                dist_stages=dist_stages, dist_fallback=dist_fallback,
                planning_ms=res.planning_ms, compile_ms=compile_ms,
                execution_ms=res.execution_ms, cache_hit=cache_hit,
                queued_ms=queued_ms, memory_blocked_ms=memory_blocked_ms,
                findings=findings,
                worst_estimate_ratio=res.worst_estimate_ratio,
            ))
            return res

        if isinstance(stmt, ast.Explain):
            validate = getattr(stmt, "validate", False)
            # EXPLAIN (TYPE VALIDATE) always gates every rewrite, like
            # it always runs the plan validator
            plan = self.binder.plan_ast(
                stmt.query, validate_rewrites=True if validate else None)
            if validate:
                # parse + bind succeeded; now the static tier: the
                # plan/IR validator (analysis/) checks type soundness,
                # null-mask policy, ladder conformance and signature
                # determinism — PlanValidationError propagates with
                # node-specific diagnostics (EXPLAIN (TYPE VALIDATE));
                # every rewrite already passed the soundness gate above
                from presto_tpu.analysis import (assert_kernel_sound,
                                                 assert_valid)
                from presto_tpu.types import BOOLEAN

                assert_valid(plan)
                # kernel-soundness tier: interval/overflow/null-policy
                # proof over every compiled expression (KernelSoundness-
                # Error carries node-attributed diagnostics)
                assert_kernel_sound(plan)
                report = getattr(plan, "_optimizer_report", None)
                summary = report.summary() if report else "optimizer: n/a"
                # which arithmetic the plan's intervals let the chains
                # compile without its runtime guard, site by site
                sites = self.executor.arith_report(plan)
                if sites:
                    summary += "\narithmetic:\n  " + "\n  ".join(sites)
                return MaterializedResult(
                    ["Valid", "Optimizer"], [BOOLEAN, VARCHAR],
                    [(True, summary)])
            if getattr(stmt, "distributed", False):
                from presto_tpu.parallel.fragment import explain_distributed

                text = explain_distributed(
                    plan, catalog=self.catalog,
                    min_stage_rows=int(
                        self.session.get("distributed_min_stage_rows")))
                return MaterializedResult(["Query Plan"], [VARCHAR], [(text,)])
            if stmt.analyze and getattr(stmt, "verbose", False):
                # the verbose re-execution runs under its own tracer +
                # timeline so the doctor can append a `diagnosis:` block
                # (EXPLAIN has no client query id; a synthetic one keys
                # the registries like any other query)
                from presto_tpu import obs
                from presto_tpu.events import new_query_id

                qid = query_id or new_query_id()
                tracer = obs.register(obs.Tracer(qid))
                timeline = obs.ensure_timeline(qid)
                progress = obs.register_progress(obs.QueryProgress(qid))
                t1 = time.perf_counter()
                with obs.tracing(tracer), obs.publishing(progress), \
                        obs.recording(timeline):
                    text = self.executor.explain_analyze_verbose(plan)
                wall_ms = (time.perf_counter() - t1) * 1e3
                progress.mark_done()
                findings = [f.as_dict() for f in obs.doctor.diagnose(
                    qid, tracer=tracer, timeline=timeline,
                    progress=progress, wall_ms=wall_ms)]
                if timeline is not None:
                    timeline.annotate("findings", findings)
                text = obs.doctor.format_findings(findings) + "\n" + text
            elif stmt.analyze:
                stats = QueryStats()
                stats.register_plan(plan)
                if self.session.get("distributed"):
                    # a distributed session's ANALYZE must execute on
                    # the tier the query would actually use — running
                    # local-only silently dropped every worker-fragment
                    # operator from the output
                    self._distributed().run(plan, stats=stats)
                else:
                    self.executor.stats = stats
                    try:
                        self.executor.run(plan)
                    finally:
                        self.executor.stats = None
                text = self.executor.explain_with_stats(
                    plan, stats, misestimate_factor=float(
                        self.session.get("misestimate_factor")))
                # analyze runs feed the plan-history store like any
                # stats-collecting execution
                from presto_tpu.obs.history import default_history

                default_history().record_query(
                    stats, getattr(plan, "_estimates", None))
            else:
                text = self.executor.explain(plan)
            return MaterializedResult(["Query Plan"], [VARCHAR], [(text,)])

        if isinstance(stmt, (ast.Grant, ast.Revoke)):
            ac = self.access_control
            chk = getattr(ac, "check_can_grant", None)
            if chk is not None:
                chk(self.session.user)  # no self-escalation
            fn = getattr(ac, "grant" if isinstance(stmt, ast.Grant)
                         else "revoke", None)
            if fn is None:
                raise ValueError(
                    "the active access control does not support GRANT/REVOKE"
                    " (use GrantingAccessControl)")
            fn(stmt.grantee, stmt.table, stmt.privileges)
            word = "GRANT" if isinstance(stmt, ast.Grant) else "REVOKE"
            return MaterializedResult(["result"], [VARCHAR], [(word,)])

        if isinstance(stmt, ast.AlterTableRename):
            handle = self.catalog.resolve(stmt.name)
            conn = self.catalog.connector(handle.connector_name)
            self._check_tx_writable(handle.connector_name, conn)
            self.access_control.check_can_write(self.session.user,
                                                 handle.table)
            if not hasattr(conn, "rename_table"):
                raise ValueError(
                    f"connector {handle.connector_name} does not support "
                    "ALTER TABLE RENAME")
            new_bare = stmt.new_name.split(".")[-1]
            conn.rename_table(handle.table, new_bare)
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("RENAME",)])

        if isinstance(stmt, ast.SetSession):
            self.session.set(stmt.name, stmt.value)
            # executor knobs may have changed; rebuild (plans survive)
            self.executor = self._make_executor()
            self._dist = None  # mesh/session knobs re-resolve lazily
            return MaterializedResult(["result"], [VARCHAR], [("SET SESSION",)])

        if isinstance(stmt, ast.ShowSession):
            rows = [
                (name, str(value), str(default), desc)
                for name, value, default, desc in self.session.describe()
            ]
            return MaterializedResult(
                ["name", "value", "default", "description"], [VARCHAR] * 4, rows
            )

        if isinstance(stmt, ast.StartTransaction):
            from presto_tpu.transaction import TransactionError

            if self._open_tx is not None:
                raise TransactionError("a transaction is already open")
            self._open_tx = self.transactions.begin(read_only=stmt.read_only)
            return MaterializedResult(["result"], [VARCHAR], [("START TRANSACTION",)])

        if isinstance(stmt, ast.Commit):
            from presto_tpu.transaction import TransactionError

            if self._open_tx is None:
                raise TransactionError("no transaction is open")
            tx, self._open_tx = self._open_tx, None
            self.transactions.commit(tx.tx_id)
            self._invalidate_plans()  # published writes change table state
            return MaterializedResult(["result"], [VARCHAR], [("COMMIT",)])

        if isinstance(stmt, ast.Rollback):
            from presto_tpu.transaction import TransactionError

            if self._open_tx is None:
                raise TransactionError("no transaction is open")
            tx, self._open_tx = self._open_tx, None
            self.transactions.rollback(tx.tx_id)
            return MaterializedResult(["result"], [VARCHAR], [("ROLLBACK",)])

        if isinstance(stmt, (ast.CreateTableAs, ast.InsertInto)):
            return self._write(stmt, query_id=query_id)

        if isinstance(stmt, ast.DropTable):
            # drops route through access control exactly like writes
            # (AccessControlManager.checkCanDropTable analog)
            handle = self.catalog.resolve(stmt.name)
            # access rules key on bare table names
            self.access_control.check_can_write(self.session.user, handle.table)
            conn = self.catalog.connector(handle.connector_name)
            if not hasattr(conn, "drop_table"):
                raise ValueError(f"connector {handle.connector_name} is read-only")
            self._check_tx_writable(handle.connector_name, conn)
            if self._stage_write(handle.connector_name, conn, "drop_table", handle.table):
                return MaterializedResult(["result"], [VARCHAR], [("DROP TABLE (staged)",)])
            conn.drop_table(handle.table)
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("DROP TABLE",)])

        if isinstance(stmt, ast.Prepare):
            self._prepared[stmt.name] = stmt.query
            return MaterializedResult(["result"], [VARCHAR], [("PREPARE",)])

        if isinstance(stmt, ast.Execute):
            q = self._prepared.get(stmt.name)
            if q is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            bound = _substitute_params(q, list(stmt.params))
            # parameters make each execution a distinct plan; don't
            # pollute the text-keyed plan cache
            plan = self._validated(self.binder.plan_ast(bound))
            self._check_access(plan)
            return self.executor.run(plan, query_id=query_id)

        if isinstance(stmt, ast.Deallocate):
            if self._prepared.pop(stmt.name, None) is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            return MaterializedResult(["result"], [VARCHAR], [("DEALLOCATE",)])

        if isinstance(stmt, ast.ShowCatalogs):
            names = sorted(self.catalog._connectors)
            return MaterializedResult(["catalog"], [VARCHAR], [(n,) for n in names])

        if isinstance(stmt, ast.ShowFunctions):
            from presto_tpu.sql.binder import AGG_FUNCTIONS, SCALAR_FUNCTIONS

            window = ["rank", "dense_rank", "row_number", "ntile",
                      "percent_rank", "cume_dist", "lead", "lag",
                      "first_value", "last_value", "nth_value"]
            rows = sorted(
                [(f, "scalar") for f in SCALAR_FUNCTIONS]
                + [(f, "aggregate") for f in AGG_FUNCTIONS]
                + [(f, "window") for f in window]
            )
            return MaterializedResult(["function", "kind"], [VARCHAR, VARCHAR], rows)

        if isinstance(stmt, (ast.DescribeOutput, ast.DescribeInput)):
            q = self._prepared.get(stmt.name)
            if q is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            if isinstance(stmt, ast.DescribeInput):
                # parameter positions; deviation (PARITY.md): every
                # type reports 'unknown' — the reference's
                # DescribeInputRewrite infers types from the parameter
                # context, which this binder does not track
                n = _count_parameters(q)
                rows = [(i, "unknown") for i in range(n)]
                return MaterializedResult(
                    ["Position", "Type"], [BIGINT, VARCHAR], rows)
            # DESCRIBE OUTPUT: bind with NULL parameters to recover the
            # projected column names/types (DescribeOutputRewrite)
            n = _count_parameters(q)
            filled = _substitute_params(q, tuple(ast.NullLit()
                                                 for _ in range(n)))
            plan = self.binder.plan_ast(filled)
            self._check_access(plan)  # no schema leaks on denied tables
            rows = [(nm, repr(t)) for nm, t in
                    zip(plan.output_names, plan.output_types)]
            return MaterializedResult(
                ["Column Name", "Type"], [VARCHAR, VARCHAR], rows)

        if isinstance(stmt, ast.ResetSession):
            self.session.reset(stmt.name)
            # executor knobs may have changed; rebuild (plans survive)
            self.executor = self._make_executor()
            self._dist = None  # mesh/session knobs re-resolve lazily
            return MaterializedResult(["result"], [VARCHAR],
                                      [("RESET SESSION",)])

        if isinstance(stmt, ast.ShowCreateTable):
            handle = self.catalog.resolve(stmt.table)
            cols = ",\n".join(f"   {c.name} {c.type!r}"
                              for c in handle.columns)
            ddl = (f"CREATE TABLE {stmt.table} (\n{cols}\n)")
            return MaterializedResult(["Create Table"], [VARCHAR], [(ddl,)])

        if isinstance(stmt, ast.ShowStats):
            # ShowStatsRewrite.java's table shape: one row per column +
            # the summary row carrying row_count.  Domains live in
            # DEVICE representation (dictionary codes, epoch days,
            # scaled decimal ints) — convert to logical values here.
            import datetime as _dt

            from presto_tpu.types import DOUBLE

            def logical(c, v):
                if v is None:
                    return None
                t = c.type
                if t.is_string:
                    return None  # codes say nothing about value order
                if t.name == "date":
                    return str(_dt.date(1970, 1, 1)
                               + _dt.timedelta(days=int(v)))
                if t.is_decimal:
                    return str(v / 10 ** (t.scale or 0))
                return str(v)

            handle = self.catalog.resolve(stmt.table)
            rows = []
            for c in handle.columns:
                ndv = c.ndv
                if ndv is None and c.dictionary is not None:
                    ndv = len(c.dictionary)
                if ndv is None and c.domain is not None \
                        and c.type.is_integerlike:
                    # width == ndv only for unscaled integer domains
                    ndv = c.domain[1] - c.domain[0] + 1
                lo, hi = (c.domain if c.domain is not None else (None, None))
                if c.type.is_string and c.dictionary is not None:
                    vals = c.dictionary.values
                    lo_s, hi_s = (min(vals), max(vals)) if vals else (None, None)
                else:
                    lo_s, hi_s = logical(c, lo), logical(c, hi)
                rows.append((c.name, float(ndv) if ndv is not None else None,
                             lo_s, hi_s, None))
            rows.append((None, None, None, None, float(handle.row_count)))
            return MaterializedResult(
                ["column_name", "distinct_values_count", "low_value",
                 "high_value", "row_count"],
                [VARCHAR, DOUBLE, VARCHAR, VARCHAR, DOUBLE], rows)

        if isinstance(stmt, ast.Describe):
            rows = self._columns_of(stmt.table)
            return MaterializedResult(["column", "type"], [VARCHAR, VARCHAR], rows)

        if isinstance(stmt, ast.Delete):
            return self._delete(stmt, query_id=query_id)

        if isinstance(stmt, ast.ShowTables):
            names = sorted(
                set(
                    t
                    for cname in self.catalog._connectors
                    for t in self.catalog.connector(cname).table_names()
                )
                | {k[2] for k in self.catalog._views}  # views list too
            )
            return MaterializedResult(["table"], [VARCHAR], [(n,) for n in names])

        if isinstance(stmt, ast.ShowColumns):
            rows = self._columns_of(stmt.table)
            return MaterializedResult(["column", "type"], [VARCHAR, VARCHAR], rows)

        if isinstance(stmt, ast.Use):
            cat = stmt.catalog or self.session.catalog
            if cat is None:
                raise ValueError("USE schema requires a current catalog "
                                 "(USE catalog.schema)")
            if cat not in self.catalog._connectors:
                raise ValueError(f"catalog not found: {cat}")
            if not self.catalog.has_schema(cat, stmt.schema):
                raise ValueError(f"schema not found: {cat}.{stmt.schema}")
            self.session.catalog = cat
            self.session.schema = stmt.schema
            self._invalidate_plans()  # name resolution changed
            return MaterializedResult(["result"], [VARCHAR], [("USE",)])

        if isinstance(stmt, ast.CreateView):
            # bind now so a broken view fails at CREATE, store the text
            # (CreateViewTask.java:44 analyzes the view statement first)
            self.binder.plan(stmt.sql)
            try:
                self.catalog.resolve(stmt.name, session=self.session)
                raise ValueError(
                    f"a table with that name already exists: {stmt.name}")
            except KeyError:
                pass
            self.access_control.check_can_write(
                self.session.user, stmt.name.split(".")[-1])
            self.catalog.create_view(stmt.name, stmt.sql,
                                     session=self.session,
                                     replace=stmt.replace)
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("CREATE VIEW",)])

        if isinstance(stmt, ast.DropView):
            self.access_control.check_can_write(
                self.session.user, stmt.name.split(".")[-1])
            self.catalog.drop_view(stmt.name, session=self.session,
                                   if_exists=stmt.if_exists)
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("DROP VIEW",)])

        if isinstance(stmt, ast.CreateSchema):
            cat = stmt.catalog or self.session.catalog
            if cat is None:
                raise ValueError("CREATE SCHEMA requires a catalog")
            self.catalog.create_schema(cat, stmt.name,
                                       if_not_exists=stmt.if_not_exists)
            return MaterializedResult(["result"], [VARCHAR],
                                      [("CREATE SCHEMA",)])

        if isinstance(stmt, ast.DropSchema):
            cat = stmt.catalog or self.session.catalog
            if cat is None:
                raise ValueError("DROP SCHEMA requires a catalog")
            self.catalog.drop_schema(cat, stmt.name,
                                     if_exists=stmt.if_exists,
                                     cascade=stmt.cascade)
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("DROP SCHEMA",)])

        if isinstance(stmt, ast.RenameSchema):
            cat = stmt.catalog or self.session.catalog
            if cat is None:
                raise ValueError("ALTER SCHEMA requires a catalog")
            self.catalog.rename_schema(cat, stmt.name, stmt.new_name)
            if self.session.catalog == cat and self.session.schema == stmt.name:
                self.session.schema = stmt.new_name
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [("ALTER SCHEMA",)])

        if isinstance(stmt, ast.ShowSchemas):
            cat = stmt.catalog or self.session.catalog
            if cat is not None:
                rows = [(s,) for s in self.catalog.schemas(cat)]
            else:  # no catalog context: union over catalogs
                seen = sorted({s for c in self.catalog._connectors
                               for s in self.catalog.schemas(c)})
                rows = [(s,) for s in seen]
            return MaterializedResult(["Schema"], [VARCHAR], rows)

        if isinstance(stmt, (ast.AddColumn, ast.DropColumn)):
            handle = self.catalog.resolve(stmt.table, session=self.session)
            self.access_control.check_can_write(self.session.user,
                                                handle.table.split(".")[-1])
            conn = self.catalog.connector(handle.connector_name)
            self._check_tx_writable(handle.connector_name, conn)
            if isinstance(stmt, ast.AddColumn):
                if not hasattr(conn, "add_column"):
                    raise ValueError(
                        f"connector {handle.connector_name} does not "
                        "support ADD COLUMN")
                from presto_tpu.types import parse_type

                conn.add_column(handle.table, stmt.column,
                                parse_type(stmt.type_name))
                msg = "ADD COLUMN"
            else:
                if not hasattr(conn, "drop_column"):
                    raise ValueError(
                        f"connector {handle.connector_name} does not "
                        "support DROP COLUMN")
                conn.drop_column(handle.table, stmt.column)
                msg = "DROP COLUMN"
            self._invalidate_plans()
            return MaterializedResult(["result"], [VARCHAR], [(msg,)])

        if isinstance(stmt, ast.ShowPartitions):
            handle = self.catalog.resolve(stmt.table, session=self.session)
            conn = self.catalog.connector(handle.connector_name)
            pcols = (conn.partition_columns(handle.table)
                     if hasattr(conn, "partition_columns") else [])
            if not pcols or not hasattr(conn, "partitions"):
                raise ValueError(f"table is not partitioned: {stmt.table}")
            rows = [tuple(p.get(c) for c in pcols)
                    for p in conn.partitions(handle.table)]
            types = {c.name: c.type for c in handle.columns}
            return MaterializedResult(
                list(pcols), [types.get(c, VARCHAR) for c in pcols], rows)

        if isinstance(stmt, ast.SetPath):
            self.session.path = stmt.path
            return MaterializedResult(["result"], [VARCHAR], [("SET PATH",)])

        if isinstance(stmt, ast.Call):
            return self._call_procedure(stmt)

        raise ValueError(f"unsupported statement {stmt!r}")

    def _columns_of(self, name: str):
        """(column, type) rows for a table OR a view (views bind their
        stored SQL to recover the projected shape — ShowColumnsRewrite
        consults metadata.getView the same way)."""
        view = self.catalog.lookup_view(name, self.session)
        if view is not None:
            # bind under the view's creation-time namespace, exactly
            # like the binder's reference-time expansion
            vdef = view[1]
            saved = (self.session.catalog, self.session.schema)
            self.session.catalog = vdef.catalog
            self.session.schema = vdef.schema
            try:
                plan = self.binder.plan(vdef.sql)
            finally:
                self.session.catalog, self.session.schema = saved
            return [(n, repr(t))
                    for n, t in zip(plan.output_names, plan.output_types)]
        handle = self.catalog.resolve(name, session=self.session)
        return [(c.name, repr(c.type)) for c in handle.columns]

    def _call_procedure(self, stmt: ast.Call) -> MaterializedResult:
        """CALL proc(literal args) via the procedure registry
        (spi/procedure/Procedure.java + execution/CallTask.java:60 —
        kill_query ships as a procedure there too)."""
        proc = self.procedures.get(stmt.name.lower())
        if proc is None:
            raise ValueError(f"procedure not registered: {stmt.name}")

        def lit(node):
            if isinstance(node, ast.StringLit):
                return node.value
            if isinstance(node, ast.NumberLit):
                v = node.text
                return float(v) if ("." in v or "e" in v.lower()) else int(v)
            if isinstance(node, ast.NullLit):
                return None
            if isinstance(node, ast.Unary) and node.op == "-":
                return -lit(node.operand)
            raise ValueError("CALL arguments must be literals")

        out = proc(self.session, *[lit(a) for a in stmt.args])
        return MaterializedResult(["result"], [VARCHAR],
                                  [(out if out is not None else "CALL",)])

    def register_procedure(self, name: str, fn) -> None:
        """Connector/plugin procedure registration
        (spi/procedure/Procedure.java)."""
        self.procedures[name.lower()] = fn

    def _kill_query_procedure(self, session, query_id, message=None):
        """system.runtime.kill_query(query_id[, message]): fail the
        query's future memory reservations (the in-process analog of
        KillQueryProcedure.java — the coordinator overrides this with
        its query-manager kill)."""
        if self.memory_pool is None:
            raise ValueError("no memory pool; kill_query unavailable")
        freed = self.memory_pool.kill_query(str(query_id))
        return f"killed {query_id} (freed {freed} bytes)"

    def _write(self, stmt, query_id=None) -> MaterializedResult:
        """CTAS / INSERT (TableWriterOperator + TableFinishOperator
        analog: the query result lands in the writable connector and
        the row count is returned)."""
        import numpy as np

        plan = self._validated(self.binder.plan_ast(stmt.query))
        self._check_access(plan)
        if isinstance(stmt, ast.InsertInto):
            self.access_control.check_can_insert(
                self.session.user, stmt.name.split(".")[-1])
        else:
            self.access_control.check_can_write(
                self.session.user, stmt.name.split(".")[-1])

        # resolve the write target BEFORE running the source query so a
        # READ ONLY transaction / non-transactional connector rejects
        # without burning device time on the doomed SELECT
        if isinstance(stmt, ast.CreateTableAs):
            if self.catalog.lookup_view(stmt.name, self.session) is not None:
                raise ValueError(
                    f"a view with that name already exists: {stmt.name}")
            cname, table = self._write_target(stmt.name)
            conn = self.catalog.connector(cname)
        else:
            handle = self.catalog.resolve(stmt.name)
            cname, table = handle.connector_name, handle.table
            conn = self.catalog.connector(cname)
            if not hasattr(conn, "append_pages"):
                raise ValueError(f"connector {cname} is read-only")
        self._check_tx_writable(cname, conn)

        # scaled writers: per-page transfer+compaction runs on a pool
        # that grows while the producer outpaces it; results publish
        # atomically after the whole query succeeds
        # (scheduler/ScaledWriterScheduler.java + TableFinishOperator)
        from presto_tpu.exec.local import GroupCapacityExceeded
        from presto_tpu.writer import ScaledWriter

        while True:
            writer = ScaledWriter(lambda p: p.compact_host())
            done = False
            try:
                for p in self.executor.stream_pages(plan, query_id=query_id):
                    writer.submit(p)
                pages = writer.finish()
                done = True
                break
            except GroupCapacityExceeded:
                pass  # restart with the executor's larger caps
            finally:
                if not done:
                    writer.abort()  # never leak blocked writer threads
        live = [p for p in pages
                if int(np.asarray(p.row_mask).sum()) > 0]
        pages = live or pages[:1]
        rows = sum(int(np.asarray(p.row_mask).sum()) for p in pages)

        if isinstance(stmt, ast.CreateTableAs):
            schema = list(zip(plan.output_names, plan.output_types))
            props = dict(getattr(stmt, "properties", ()) or ())
            if props and not getattr(conn, "supports_table_properties", False):
                raise ValueError(
                    f"connector {cname} does not support CREATE TABLE "
                    f"properties {sorted(props)}")
            if props:
                op_args = (table, schema, pages)
                if not self._stage_write(cname, conn, "create_table",
                                         *op_args, properties=props):
                    conn.create_table(table, schema, pages, properties=props)
            elif not self._stage_write(cname, conn, "create_table", table, schema, pages):
                conn.create_table(table, schema, pages)
        else:
            want = [c.type for c in handle.columns]
            got = plan.output_types
            # name+scale equality: decimal scale decides the scaled-int
            # representation (a name-only check would let decimal(x,3)
            # data land in a decimal(x,2) column 10x off), but precision
            # is metadata — expressions widen to precision 18 and their
            # values are still valid for any column of the same scale.
            if [(t.name, t.scale) for t in want] != [(t.name, t.scale) for t in got]:
                raise ValueError(f"INSERT schema mismatch: {want} vs {got}")
            pages = [self._recode_strings(p, handle) for p in pages]
            if not self._stage_write(cname, conn, "append_pages", table, pages):
                conn.append_pages(table, pages)
        self._invalidate_plans()
        return MaterializedResult(["rows"], [BIGINT], [(rows,)])

    def _delete(self, stmt, query_id=None) -> MaterializedResult:
        """DELETE FROM t [WHERE pred] (DeleteOperator /
        MetadataDeleteOperator analog): the surviving rows re-select
        through the engine (NOT pred) and overwrite the table pages
        atomically — connector-side delete-by-rewrite, the model the
        memory connector supports."""
        import numpy as np

        handle = self.catalog.resolve(stmt.table)
        self.access_control.check_can_delete(self.session.user, handle.table)
        conn = self.catalog.connector(handle.connector_name)
        if not hasattr(conn, "create_table"):
            raise ValueError(f"connector {handle.connector_name} is read-only")
        self._check_tx_writable(handle.connector_name, conn)
        before = conn.row_count(handle.table)
        if stmt.where is None:
            keep_sql_pred = None
            survivors = []
        else:
            # survivors: NOT pred OR pred IS NULL (NULL predicates keep
            # the row, matching DELETE's true-only semantics)
            keep = ast.Query(
                select=(ast.SelectItem(ast.Star()),),
                from_=(ast.TableRef(handle.table),),
                where=ast.Binary("or", ast.Unary("not", stmt.where),
                                 ast.IsNull(stmt.where, False)),
            )
            plan = self.binder.plan_ast(keep)
            page = self.executor.run_to_page(plan, query_id=query_id).compact_host()
            survivors = [page]
        schema = conn.schema(handle.table)
        op_args = (handle.table, schema, survivors,
                   {c.name: c.domain for c in handle.columns})
        if self._stage_write(handle.connector_name, conn, "create_table", *op_args):
            return MaterializedResult(["rows"], [BIGINT], [(-1,)])
        conn.create_table(*op_args)
        self._invalidate_plans()
        after = conn.row_count(handle.table)
        return MaterializedResult(["rows"], [BIGINT], [(before - after,)])

    def _write_target(self, name: str):
        """(connector, physical table) for a CTAS target: a
        'catalog.table' prefix routes to that connector, else the USE
        defaults apply (non-default schema prefixes the physical name),
        else the default writable one."""
        if "." in name:
            cname, bare = name.split(".", 1)
            if cname in self.catalog._connectors:
                return cname, bare
        s_cat, s_sch = self.session.catalog, self.session.schema
        if ("." not in name and s_cat in self.catalog._connectors
                and hasattr(self.catalog.connector(s_cat), "create_table")):
            return s_cat, (name if s_sch in (None, "default")
                           else f"{s_sch}.{name}")
        if self.catalog.write_connector is None:
            raise ValueError("no writable connector registered")
        return self.catalog.write_connector, name

    def _check_tx_writable(self, connector_name: str, conn) -> None:
        """Early rejection for writes that cannot proceed in the open
        transaction (read-only / connector without tx hooks)."""
        if self._open_tx is None:
            return
        from presto_tpu.transaction import TransactionError

        if self._open_tx.read_only:
            raise TransactionError("transaction is READ ONLY")
        if not hasattr(conn, "begin_transaction") or not hasattr(conn, "stage"):
            raise TransactionError(
                f"connector {connector_name} does not support transactions")

    def _invalidate_plans(self) -> None:
        """Writes change split counts / stats snapshotted into cached
        plans (TableHandle.num_splits, row_count); drop them so the next
        query re-resolves metadata (the reference re-resolves per query
        — its plans are never cached across queries)."""
        self._plans.clear()

    def _stage_write(self, connector_name: str, conn, op: str, *args,
                     **kwargs) -> bool:
        """Inside an open transaction, stage the write on the connector's
        tx handle instead of applying it; returns True when staged."""
        if self._open_tx is None:
            return False
        self._check_tx_writable(connector_name, conn)
        handle = self._open_tx.handle_for(connector_name, conn)
        conn.stage(handle, op, *args, **kwargs)
        return True

    def _recode_strings(self, page, handle):
        """Recode inserted VARCHAR blocks onto the table's dictionary so
        appended pages and existing pages agree on code meaning; values
        absent from the table dictionary are rejected."""
        import numpy as np

        from presto_tpu.page import Block, Page

        blocks = list(page.blocks)
        changed = False
        conn = self.catalog.connector(handle.connector_name)
        open_cols = (conn.open_dictionary_columns(handle.table)
                     if hasattr(conn, "open_dictionary_columns") else set())
        for i, col in enumerate(handle.columns):
            if not col.type.is_string:
                continue
            if col.name in open_cols:
                # dynamic partitioning: new values extend the
                # metastore's value list instead of being rejected
                continue
            b = blocks[i]
            dst = getattr(col, "dictionary", None)
            if dst is None or b.dictionary is dst:
                continue
            src = b.dictionary
            codes = np.asarray(b.data)
            valid = np.asarray(b.valid) & np.asarray(page.row_mask)
            # O(|dictionary|) remap table + vectorized gather
            remap = np.asarray([dst.code_of(v) for v in src.values], np.int64)
            in_range = (codes >= 0) & (codes < len(remap))
            new_codes = np.where(in_range, remap[np.clip(codes, 0, len(remap) - 1)], -1)
            bad = valid & (new_codes < 0)
            if bad.any():
                j = int(np.nonzero(bad)[0][0])
                val = src.values[codes[j]] if in_range[j] else codes[j]
                raise ValueError(
                    f"INSERT value {val!r} not in dictionary of column {col.name}"
                )
            blocks[i] = Block(new_codes.astype(codes.dtype), b.valid, b.type, dst)
            changed = True
        return Page(tuple(blocks), page.row_mask) if changed else page

    def _result_cache_prepared(self, plan):
        """(key, versions) when the result cache applies to this query
        (``result_cache_enabled`` session property, deterministic plan,
        every scanned table versioned) — None otherwise."""
        try:
            if not self.session.get("result_cache_enabled"):
                return None
        except KeyError:
            return None
        from presto_tpu.serving.cache import default_result_cache

        return default_result_cache().prepare(plan, self.catalog)

    def _result_cache_hit(self, plan, prepared):
        """A MaterializedResult served from the structural result cache,
        or None on miss.  The cached row list is copied — callers (the
        coordinator's pager, verifiers) may hold results across later
        invalidations."""
        from presto_tpu.serving.cache import default_result_cache

        got = default_result_cache().lookup(prepared)
        if got is None:
            return None
        names, types, rows = got
        return MaterializedResult(list(names), list(types), list(rows))

    def _run_plan(self, plan, query_id=None, stats=None):
        """Route through the device-mesh tier when ``SET SESSION
        distributed = true`` and the plan shape distributes; otherwise
        (or on DistributedUnsupported) the local executor.  The query
        scope tags streaming-exchange buffers with the query id so a
        deadline/memory kill (pool.kill_query) aborts them and unblocks
        backpressured producer threads.

        ``stats``: per-operator actuals sink (``collect_stats`` /
        EXPLAIN ANALYZE) — threaded into whichever tier executes so
        estimate-vs-actual attribution works on every path."""
        from presto_tpu.parallel.streams import query_scope

        with query_scope(query_id):
            if self.session.get("distributed"):
                return self._distributed().run(plan, stats=stats)
            if stats is not None:
                stats.register_plan(plan)
                self.executor.stats = stats
                try:
                    return self.executor.run(plan, query_id=query_id)
                finally:
                    self.executor.stats = None
            return self.executor.run(plan, query_id=query_id)

    def _distributed(self):
        if getattr(self, "_dist", None) is None:
            from presto_tpu.parallel.dist import DistributedRunner, make_mesh

            n = self.session.get("hash_partition_count") or None
            self._dist = DistributedRunner(
                self.catalog, mesh=make_mesh(n), session=self.session)
        return self._dist

    def _plan_cached(self, sql: str, q: ast.Query):
        plan = self._plans.get(sql)
        if plan is None:
            from presto_tpu.sql.binder import BindError, annotate_position

            try:
                plan = self._validated(self.binder.plan_ast(q))
            except BindError as e:
                # statement text is known here: render the failing AST
                # node's offset as line:col in the user-facing error
                raise annotate_position(e, sql) from e.__cause__
            self._plans[sql] = plan
        return plan

    @staticmethod
    def _ms(seconds: Optional[float]) -> Optional[float]:
        return None if seconds is None else round(seconds * 1e3, 3)

    @staticmethod
    def _finalize_trace(tracer, t_q0: float) -> None:
        """Close the root ``query`` span (parse start -> now) and write
        the per-query Chrome-trace file when a trace dir is set."""
        if tracer is None:
            return
        import time

        from presto_tpu import obs

        tracer.add_complete("query", "lifecycle", t_q0,
                            time.perf_counter() - t_q0)
        obs.maybe_write_trace(tracer)

    def _check_access(self, plan) -> None:
        from presto_tpu.security import scan_tables

        for table in scan_tables(plan):
            self.access_control.check_can_select(self.session.user, table)

    def explain(self, sql: str) -> str:
        return self.executor.explain(self.plan(sql))

    def explain_distributed(self, sql: str) -> str:
        """Fragment-tree rendering (EXPLAIN (TYPE DISTRIBUTED) analog:
        sql/planner/PlanFragmenter SubPlans printed by PlanPrinter)."""
        from presto_tpu.parallel.fragment import explain_distributed

        return explain_distributed(
            self.plan(sql), catalog=self.catalog,
            min_stage_rows=int(self.session.get("distributed_min_stage_rows")))
