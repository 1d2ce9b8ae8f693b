"""Distributed query execution over a jax device mesh.

Reference analog: the distributed tier — ``PlanFragmenter.java:84``
(stage boundaries at exchanges), ``SqlStageExecution``/``TaskExecutor``
(per-node work), and the shuffle of §2.3.  TPU redesign: a stage is ONE
SPMD program ``shard_map``-ed over the mesh; "tasks" are the per-device
shards; the shuffle is ``all_to_all`` over ICI (see exchange.py); the
scheduler is the wave loop feeding each device one split per wave
(SourcePartitionedScheduler's role).

Supported distributed shape:
    [Output/Project/Sort/TopN/Limit/Filter]*
      -> Aggregation(single)
        -> streaming chain (scan -> filter/project -> joins -> ...)
Joins distribute per the fragmenter's decision
(parallel/fragment.py, DetermineJoinDistributionType.java:33 analog):
small builds replicate to every device (BROADCAST); large builds are
hash-partitioned across devices and the probe rows ride an
``all_to_all`` on the join key inside the wave program (FIXED_HASH —
the repartitioned join of AddExchanges.java:738).  Expanding
(many-to-many) joins run in-program with static output capacities and
count-check-and-retry, like the local runner.

Post-aggregation nodes run locally on the gathered (small) result via
PrecomputedNode splicing.  Anything else falls back to LocalRunner.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

_log = logging.getLogger("presto_tpu.dist")

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from presto_tpu.catalog import Catalog
from presto_tpu.exec.chain import chain_leaf, lower_chain
from presto_tpu.exec.local import (
    MAX_AGG_GROUPS,
    GroupCapacityExceeded,
    LocalRunner,
    MaterializedResult,
    QueryStats,
    concat_pages_device,
)
from presto_tpu.ops.join import JoinBuild, build_join, probe_expand, probe_join
from presto_tpu.parallel.fragment import DEFAULT_BROADCAST_THRESHOLD
from presto_tpu.expr.ir import ColumnRef
from presto_tpu.ops.aggregate import grouped_aggregate, merge_aggregate
from presto_tpu.page import Block, Page, concat_pages_host
from presto_tpu.parallel.exchange import (
    exchange_page,
    partition_for_exchange,
    partition_targets,
)
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
)


class DistributedUnsupported(Exception):
    pass


class _BuildOverflow(Exception):
    """A sharded-build exchange bucket overfilled; retry with the given
    bucket capacity."""

    def __init__(self, needed: int):
        self.needed = needed


class _ChainCtx:
    """Build-time context for a distributed chain: registered join
    builds (broadcast consts vs sharded consts) and the runtime check
    names the host must verify after each wave."""

    def __init__(self, cap: int):
        self.cap = cap  # leaf split capacity (sizes the default buckets)
        self.broadcast: Dict[str, PlanNode] = {}
        self.sharded: Dict[str, PlanNode] = {}
        self.checks: List[str] = []
        self.check_meta: List[Tuple[str, PlanNode, str]] = []
        self._i = 0

    def add_broadcast(self, node) -> str:
        key = f"build_{self._i}"
        self._i += 1
        self.broadcast[key] = node
        return key

    def add_sharded(self, node) -> str:
        key = f"sbuild_{self._i}"
        self._i += 1
        self.sharded[key] = node
        return key

    def add_check(self, node, kind: str) -> str:
        name = f"{kind}_{len(self.checks)}"
        self.checks.append(name)
        self.check_meta.append((name, node, kind))
        return name

    def sig(self, join_cfg) -> Tuple:
        """Capacity signature: compiled programs are cached per config."""
        out = []
        for name, node, _ in self.check_meta:
            cfg = join_cfg.get(node, {})
            out.append((name, cfg.get("bucket_cap"), cfg.get("out_cap")))
        return tuple(out)


def make_mesh(n_devices: Optional[int] = None, axis: str = "d") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (axis,))


class _StageSource:
    """Wave-page provider for a stage leaf (the SplitSource /
    split-scheduling analog): a table scan reads connector splits
    (device d takes split w*n+d, honoring a restricted ``splits``
    assignment); a materialized intermediate (PrecomputedNode — the
    output of an upstream stage) chunks its page rows across devices,
    playing the RemoteSourceNode role between fragments."""

    def __init__(self, runner: "DistributedRunner", leaf):
        self.runner = runner
        self.leaf = leaf
        self.n = runner.n
        if isinstance(leaf, TableScanNode):
            self.conn = runner.catalog.connector(leaf.handle.connector_name)
            self.cap = runner._split_capacity(self.conn, leaf.handle.table)
            self.split_ids = (list(leaf.splits) if leaf.splits is not None
                              else list(range(leaf.handle.num_splits)))
            self.col_idx = list(leaf.columns)
            self._pre = None
        else:
            page = leaf.page
            self._pre = [
                (np.asarray(b.data), np.asarray(b.valid), b.type, b.dictionary)
                for b in page.blocks
            ]
            self._pre_mask = np.asarray(page.row_mask)
            total = int(self._pre_mask.shape[0])
            per = max(-(-total // self.n), 1)
            self.cap = 1 << (per - 1).bit_length()
            self.split_ids = list(range(max(-(-total // self.cap), 1)))
        self.n_splits = len(self.split_ids)
        self.waves = math.ceil(self.n_splits / self.n)

    def _page_for(self, i: int) -> Page:
        if self._pre is None:
            leaf = self.leaf
            s = self.split_ids[i]
            pg = self.conn.page_for_split(leaf.handle.table, s, capacity=self.cap)
            return Page(tuple(pg.blocks[c] for c in self.col_idx), pg.row_mask)
        lo = self.split_ids[i] * self.cap
        hi = lo + self.cap
        blocks = []
        for data, valid, typ, d in self._pre:
            dd, vv = data[lo:hi], valid[lo:hi]
            if dd.shape[0] < self.cap:
                pad = self.cap - dd.shape[0]
                dd = np.concatenate(
                    [dd, np.zeros((pad,) + dd.shape[1:], dd.dtype)])
                vv = np.concatenate([vv, np.zeros(pad, vv.dtype)])
            blocks.append(Block(dd, vv, typ, d))
        mask = self._pre_mask[lo:hi]
        if mask.shape[0] < self.cap:
            mask = np.concatenate(
                [mask, np.zeros(self.cap - mask.shape[0], mask.dtype)])
        return Page(tuple(blocks), mask)

    def _empty_page(self) -> Page:
        if self._pre is None:
            leaf = self.leaf
            pg = Page.empty(
                [leaf.handle.columns[c].type for c in self.col_idx], self.cap)
            return Page(
                tuple(
                    Block(b.data, b.valid, b.type,
                          leaf.handle.columns[c].dictionary)
                    for b, c in zip(pg.blocks, self.col_idx)
                ),
                pg.row_mask,
            )
        blocks = [
            Block(np.zeros((self.cap,) + data.shape[1:], data.dtype),
                  np.zeros(self.cap, valid.dtype), typ, d)
            for data, valid, typ, d in self._pre
        ]
        return Page(tuple(blocks), np.zeros(self.cap, self._pre_mask.dtype))

    def stacked_wave(self, w: int) -> Page:
        """Host-assemble wave ``w``'s one-split-per-device stacked page
        (device d takes split w*n + d; missing splits pad empty)."""
        pages = []
        for d in range(self.n):
            s = w * self.n + d
            pages.append(self._page_for(s) if s < self.n_splits
                         else self._empty_page())
        return _stack_pages(pages)


def _squeeze(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze(tree):
    return jax.tree.map(lambda x: x[None], tree)


class DistributedRunner:
    """Runs plans over a mesh; falls back to LocalRunner when the plan
    shape isn't distributable yet."""

    def __init__(
        self,
        catalog: Catalog,
        mesh: Optional[Mesh] = None,
        axis: str = "d",
        broadcast_threshold: int = DEFAULT_BROADCAST_THRESHOLD,
        session=None,
    ):
        from presto_tpu.parallel.fragment import DEFAULT_MIN_STAGE_ROWS

        self.catalog = catalog
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = axis
        self.broadcast_threshold = broadcast_threshold
        # session controls (SystemSessionProperties analogs)
        self.join_distribution_type = "AUTOMATIC"
        self.allow_colocated = True
        self.min_stage_rows = DEFAULT_MIN_STAGE_ROWS
        # streaming exchange knobs (parallel/streams.py): stage
        # boundaries stream pages through token-acked buffers by
        # default; off = materialize-then-consume (the A/B leg)
        from presto_tpu.parallel.streams import (
            exchange_buffer_bytes_default, exchange_streaming_default,
        )

        self.exchange_streaming = exchange_streaming_default()
        self.exchange_buffer_bytes = exchange_buffer_bytes_default()
        self.merge_fanin = 8  # sorted runs merged per consumer batch
        # serving tier: reuse warm stage intermediates at exchange
        # boundaries when signature + table versions match
        # (serving/cache.py; subplan_cache_enabled session property)
        self.subplan_cache_enabled = False
        if session is not None:
            self.join_distribution_type = session.get("join_distribution_type")
            self.allow_colocated = bool(session.get("colocated_join"))
            self.min_stage_rows = int(
                session.get("distributed_min_stage_rows"))
            self.exchange_streaming = bool(session.get("exchange_streaming"))
            eb = int(session.get("exchange_buffer_bytes"))
            if eb > 0:
                self.exchange_buffer_bytes = eb
            self.merge_fanin = max(2, int(session.get("exchange_merge_fanin")))
            self.subplan_cache_enabled = bool(
                session.get("subplan_cache_enabled"))
        # morsel-scheduler knobs flow into the mesh tier too: the local
        # fallback runner schedules its scan splits, and the wave loops
        # prefetch the next wave's host assembly while the device mesh
        # executes the current one (resolved ONCE here, per env-read)
        from presto_tpu.exec.tasks import (
            task_concurrency_default, task_prefetch_default,
        )

        tc = int(session.get("task_concurrency")) if session is not None \
            else 0
        tp = int(session.get("task_prefetch")) if session is not None else -1
        self.task_concurrency = tc if tc > 0 else task_concurrency_default()
        self.wave_prefetch = tp if tp >= 0 else task_prefetch_default()
        self.local = LocalRunner(catalog, task_concurrency=tc or None,
                                 task_prefetch=tp)
        # persistent un-jitted runner for stage building/builds: its
        # _agg_overrides must survive GroupCapacityExceeded retries
        # (a build-side aggregation overflow records its doubled
        # capacity here; a throwaway runner would loop forever)
        self._stage_runner = LocalRunner(catalog, jit=False)
        self._wave_fns: Dict[Tuple, object] = {}
        self._final_fns: Dict[Tuple, object] = {}
        self._mg_overrides: Dict[PlanNode, int] = {}
        self._join_cfg: Dict[PlanNode, Dict[str, int]] = {}
        self._sharded_builds: Dict[Tuple, JoinBuild] = {}

    @property
    def n(self) -> int:
        return self.mesh.devices.size

    # ------------------------------------------------------------------
    def run(self, plan: PlanNode,
            stats: Optional["QueryStats"] = None) -> MaterializedResult:
        """Execute distributed; on an undistributable plan fall back to
        the coordinator LOUDLY: the reason is logged, kept on
        ``last_fallback_reason``, and surfaced through query events and
        EXPLAIN (TYPE DISTRIBUTED)'s FRAGMENTED header (VERDICT r3:
        silent local fallback hid that no TPC-DS query distributed).

        ``stats``: estimate-vs-actual roll-up sink — mesh stage roots
        record their materialized output at the stage boundary, glue
        breakers and the residual root record through the coordinator
        runner's per-thread sink."""
        self.last_stage_count = 0
        self.last_fallback_reason = None
        if stats is not None:
            stats.register_plan(plan)  # idempotent — shared key space
            self.local.stats = stats
        try:
            # per-run outcome rides the RESULT (dist_stages attached by
            # _run_distributed from its local stage count): concurrent
            # queries on one runner must not report each other's stats
            out = self._run_distributed(plan, stats)
            out.dist_fallback = None
            return out
        except DistributedUnsupported as e:
            from presto_tpu.obs import METRICS

            reason = str(e) or type(e).__name__
            self.last_fallback_reason = reason
            METRICS.counter("dist.fallbacks").inc()
            _log.warning("distributed execution fell back to coordinator: %s",
                         reason)
            out = self.local.run(plan)
            out.dist_stages = 0
            out.dist_fallback = reason
            return out
        finally:
            if stats is not None:
                self.local.stats = None

    def _run_distributed(self, plan: PlanNode,
                         qstats: Optional["QueryStats"] = None,
                         ) -> MaterializedResult:
        """Generalized stage-DAG execution (PlanFragmenter.java:84 +
        SqlQueryScheduler.java:441 analog): ``lower_stages`` decomposes
        ANY plan bottom-up into mesh stages — aggregation stages and
        streaming-chain stages, whose leaves are table scans or the
        materialized output of a previously-executed stage — with glue
        breakers (sort/window/union/limit/unnest) evaluated on the
        coordinator between stages.  The residual plan (the reference's
        SINGLE root fragment) runs locally over the spliced results."""
        from presto_tpu.parallel.fragment import (
            lower_stages, undistributable_reason,
        )

        # fresh join builds per query, like LocalRunner.run_to_page's
        # per-run _builds.clear(): table data may have changed since the
        # last run (a stale build would join fresh probe rows against
        # old build rows)
        self._stage_runner._builds.clear()
        self._sharded_builds.clear()

        # live progress: one entry per mesh stage as the scheduler
        # launches it (stage-level; the scans inside each stage publish
        # their own splits-done/total through the local runner)
        from presto_tpu.obs import current_progress

        prog = current_progress()

        def _staged(prefix, node, run):
            t0 = time.perf_counter()
            if prog is None:
                page = run()
            else:
                name = prog.new_stage_name(prefix)
                prog.stage(name, splits_total=1)
                page = run()
                prog.split_done(name)
                prog.finish_stage(name)
            # estimate-vs-actual: a mesh stage's output is the one
            # place the ORIGINAL node's actual is observable (sharded
            # internals run rebuilt partial-step shapes)
            if qstats is not None and qstats.actual_rows(node) is None:
                import numpy as _np

                rows = int(_np.asarray(page.row_mask).sum())
                try:
                    from presto_tpu.memory import page_bytes
                    nb = page_bytes(page)
                except Exception:
                    nb = 0
                qstats.record(node, time.perf_counter() - t0, rows, nb)
            return page

        def run_agg(node: AggregationNode) -> PrecomputedNode:
            page = _staged("dist:aggregation", node, lambda: self._cached_stage(
                "agg", node, lambda: self.run_aggregation_stage(node)))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_chain(node: PlanNode, bound=None) -> PrecomputedNode:
            page = _staged("dist:chain", node, lambda: self._cached_stage(
                "chain", node, lambda: self.run_chain_stage(node, bound),
                bound=bound))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def eval_glue(node: PlanNode) -> PrecomputedNode:
            # runs through self.local on this thread — the per-thread
            # stats sink records it like any coordinator operator
            page = self.local.run_to_page(node)
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_window(node) -> PrecomputedNode:
            page = _staged("dist:window", node, lambda: self._cached_stage(
                "window", node, lambda: self.run_window_stage(node)))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_sort(node) -> PrecomputedNode:
            page = _staged("dist:sort", node, lambda: self._cached_stage(
                "sort", node, lambda: self.run_sort_stage(node)))
            return PrecomputedNode(page=page, channel_list=node.channels)

        def run_union(node) -> PrecomputedNode:
            page = _staged("dist:union", node,
                           lambda: self.run_union_stage(node))
            return PrecomputedNode(page=page, channel_list=node.channels)

        splices: List = []
        try:
            n_stages, root = lower_stages(
                plan, run_agg, run_chain, eval_glue, splices,
                min_stage_rows=self.min_stage_rows,
                run_window=run_window, run_sort=run_sort,
                run_union=run_union)
            if n_stages == 0:
                raise DistributedUnsupported(undistributable_reason(plan))
            from presto_tpu.obs import METRICS

            METRICS.counter("dist.stages_total").inc(n_stages)
            self.last_stage_count = n_stages
            out = self.local.run(root)
            if root is not plan:  # the whole plan was one stage
                out.names, out.types = plan.output_names, plan.output_types
            # per-run stage count from the LOCAL n_stages, not the
            # shared field a concurrent run may have reset
            out.dist_stages = n_stages
            return out
        finally:
            from presto_tpu.parallel.fragment import set_child

            for parent, slot, old in reversed(splices):
                set_child(parent, slot, old)

    # ------------------------------------------------------------------
    def _cached_stage(self, kind: str, node: PlanNode, thunk,
                      bound=None) -> Page:
        """Subplan (fragment) cache at the exchange boundary: when the
        stage rooted at ``node`` is cacheable (deterministic, leaves
        are versioned base-table scans) and its structural signature +
        table versions match a prior execution, the warm intermediate
        page is reused instead of re-executing the stage — the shared
        scan->filter->agg prefix of dashboard variants.  The mesh width
        and any consumer shard bound fold into the key (they shape the
        materialized page)."""
        if not self.subplan_cache_enabled:
            return thunk()
        from presto_tpu.exec.programs import ir_signature
        from presto_tpu.serving.cache import (
            default_subplan_cache, signature_has_identity_keys,
        )

        extra = [kind, self.n]
        if bound is not None:
            # the bound's SHALLOW shape only (count + sort spec) — its
            # source is the stage subtree prepare() signs anyway, and
            # re-walking it here would sign the whole plan twice
            bkey = (type(bound).__name__, bound.count,
                    ir_signature(tuple(getattr(bound, "sort_exprs", ())
                                       or ())),
                    ir_signature(tuple(getattr(bound, "ascending", ())
                                       or ())),
                    ir_signature(tuple(getattr(bound, "nulls_first", ())
                                       or ())))
            if signature_has_identity_keys(bkey):
                return thunk()
            extra += [bkey]
        cache = default_subplan_cache()
        prepared = cache.prepare(node, self.catalog, extra=tuple(extra))
        if prepared is not None:
            page = cache.lookup(prepared)
            if page is not None:
                return page
        page = thunk()
        cache.store(prepared, page)
        return page

    def run_chain_stage(self, chain_root: PlanNode, bound=None) -> Page:
        """Wave-execute a pure streaming chain over the mesh and gather
        its rows — a SOURCE fragment whose consumer is the coordinator
        (or a glue breaker).  ``bound`` is a consuming TopN/Limit node:
        each shard then ships only its own top/first ``bound.count``
        rows across the gather (CreatePartialTopN.java role; the glue
        breaker still runs the global pick on the coordinator)."""
        from presto_tpu.obs import span

        source = self._stage_source(chain_root)
        with span("dist_stage:chain", cat="exchange"):
            while True:
                try:
                    pages = self._run_chain_stage_once(chain_root, source,
                                                       bound)
                    break
                except GroupCapacityExceeded:
                    continue  # join capacities bumped; re-execute
            return concat_pages_host(pages)

    def _run_chain_stage_once(self, chain_root: PlanNode,
                              source: "_StageSource", bound=None,
                              sort=None, emit=None) -> List[Page]:
        """One attempt of a chain stage's wave loop.  ``sort`` (a
        SortNode consumer) appends a per-shard sort to the wave program
        so every emitted page is a pre-sorted run (the distributed-sort
        producer half, CreatePartialTopN's unbounded sibling).
        ``emit`` streams each per-device page to the consuming stage as
        soon as it is verified: immediately when the stage carries no
        runtime checks (nothing can invalidate a page), after the
        host-side check pass otherwise (an exchange-bucket overflow
        would retry the stage and re-emit)."""
        from presto_tpu.ops.sort import (
            limit_compact_page, sort_page, topn_compact_page,
        )
        from presto_tpu.planner.plan import TopNNode as _TopN

        ctx = _ChainCtx(source.cap)
        stage = self._build_dist_stage(chain_root, ctx)
        if bound is not None and bound.count >= source.cap:
            bound = None  # nothing to shrink
        runner = self._stage_runner
        consts_rep = {
            key: runner._materialize_build(j) for key, j in ctx.broadcast.items()
        }
        consts_shard = {
            key: (self._materialize_build_colocated(j)
                  if self._join_mode(j) == "colocated"
                  else self._materialize_build_sharded(j))
            for key, j in ctx.sharded.items()
        }
        mesh, axis, n = self.mesh, self.axis, self.n

        def per_device_wave(page1, consts_r, consts_s):
            page = _squeeze(page1)
            p, checks = stage(page, {**consts_r, **consts_s})
            if bound is not None:
                if isinstance(bound, _TopN):
                    p = topn_compact_page(p, bound.sort_exprs,
                                          bound.ascending, bound.count,
                                          bound.nulls_first)
                else:
                    p = limit_compact_page(p, bound.count)
            if sort is not None:
                p = sort_page(p, list(sort.sort_exprs), list(sort.ascending),
                              sort.nulls_first)
            return _unsqueeze(p), {k: v[None] for k, v in checks.items()}

        bound_key = (None if bound is None else
                     (type(bound).__name__, bound.count,
                      tuple(getattr(bound, "sort_exprs", ()) or ()),
                      tuple(getattr(bound, "ascending", ()) or ()),
                      tuple(getattr(bound, "nulls_first", ()) or ())))
        sort_key = (None if sort is None else
                    (tuple(sort.sort_exprs), tuple(sort.ascending),
                     tuple(sort.nulls_first or ())))
        fn_key = (chain_root, "chain", ctx.sig(self._join_cfg), bound_key,
                  sort_key)
        wave_fn = self._wave_fns.get(fn_key)
        if wave_fn is None:
            check_specs = {name: P(axis) for name in ctx.checks}
            wave_fn = jax.jit(
                shard_map(
                    per_device_wave, mesh=mesh,
                    in_specs=(P(axis), P(), {k: P(axis) for k in consts_shard}),
                    out_specs=(P(axis), check_specs),
                )
            )
            self._wave_fns[fn_key] = wave_fn

        sharding = NamedSharding(mesh, P(axis))
        out_pages: List[Page] = []
        wave_checks = []
        channels = chain_root.channels
        stream_now = emit is not None and not ctx.checks
        for stacked in self._wave_iter(source, sharding):
            out, cks = wave_fn(stacked, consts_rep, consts_shard)
            wave_checks.append(cks)
            pages = _unstack_pages(jax.device_get(out), channels)
            if stream_now:
                for p in pages:
                    emit(p)
            else:
                out_pages.extend(pages)
        self._verify_checks(chain_root, ctx, wave_checks, 0, False)
        if emit is not None and not stream_now:
            for p in out_pages:
                emit(p)
            return []
        return out_pages

    def _wave_iter(self, source: "_StageSource", sharding):
        """Device-placed wave pages, with the NEXT wave's host assembly
        (+ transfer) prefetched while the mesh executes the current one
        (double-buffering; wave_prefetch=0 keeps the serial loop)."""
        waves = (jax.device_put(source.stacked_wave(w), sharding)
                 for w in range(source.waves))
        if self.wave_prefetch <= 0 or self.task_concurrency <= 1:
            return waves
        from presto_tpu.exec.tasks import prefetch_iter

        return prefetch_iter(waves, depth=self.wave_prefetch,
                             name="dist-wave")

    # ------------------------------------------------------------------
    # streaming breaker stages: window / sort / union run ON the mesh
    # instead of as coordinator glue, their pages travelling through
    # the token-acked exchange (parallel/streams.py) so the consumer
    # side (bucket routing, run merging, offset mapping) overlaps the
    # producing waves
    # ------------------------------------------------------------------
    def _exchange(self, kind: str, name: str):
        from presto_tpu.parallel.streams import StreamingExchange

        return StreamingExchange(kind, name,
                                 streaming=self.exchange_streaming,
                                 max_bytes=self.exchange_buffer_bytes)

    def _produce_chain_into(self, chain_root: PlanNode, put,
                            sort=None) -> None:
        """Producer body for a streamed chain stage: wave-execute and
        put per-device pages, retrying capacity bumps internally (pages
        are only emitted once they cannot be invalidated, so a retry
        never re-emits)."""
        source = self._stage_source(chain_root)
        while True:
            try:
                self._run_chain_stage_once(chain_root, source, None,
                                           sort=sort, emit=put)
                return
            except GroupCapacityExceeded:
                continue

    def run_sort_stage(self, node) -> Page:
        """Distributed ORDER BY: every shard sorts its wave output
        in-program (ops/sort.py), the pre-sorted runs stream to the
        coordinator, and a fan-in-bounded k-way merge (ops/merge.py)
        folds runs as they arrive — MergeOperator.java:45's shape with
        the merge overlapped against still-running waves."""
        from presto_tpu.obs import span
        from presto_tpu.ops.merge import merge_sorted_pages

        sort_args = (list(node.sort_exprs), list(node.ascending),
                     node.nulls_first)
        with span("dist_stage:sort", cat="exchange"):
            ex = self._exchange("merge", "dist:sort")
            stream = ex.stream()
            ex.run(stream, lambda st: self._produce_chain_into(
                node.source, st.put, sort=node))
            runs: List[Page] = []
            try:
                for p in stream.drain():
                    runs.append(p)
                    if len(runs) >= self.merge_fanin:
                        runs = [merge_sorted_pages(runs, *sort_args)]
            except BaseException:
                ex.abort()
                raise
            finally:
                # always reap the producer thread: an orphan would keep
                # executing mesh waves into the next query's state
                ex.join()
            if not runs:
                return Page.empty([c.type for c in node.channels], 1)
            return merge_sorted_pages(runs, *sort_args)

    def run_window_stage(self, node) -> Page:
        """Distributed window: the source chain's pages stream off the
        mesh and hash-route on the PARTITION BY keys into one bucket
        per device (the FIXED_HASH exchange, host-side at this tier) —
        routing overlaps the producing waves; then one shard_map'd
        ``ops/window.py`` program evaluates every device's complete
        partitions in parallel."""
        from presto_tpu.exec.spill import make_bucket_fn
        from presto_tpu.obs import span

        n = self.n
        with span("dist_stage:window", cat="exchange"):
            ex = self._exchange("hash", "dist:window")
            stream = ex.stream()
            ex.run(stream, lambda st: self._produce_chain_into(
                node.source, st.put))
            # memoized like the window program below: a fresh jit
            # wrapper per query would recompile the hash-routing kernel
            bucket_key = (node, "window_buckets", n)
            bucket_fn = self._wave_fns.get(bucket_key)
            if bucket_fn is None:
                bucket_fn = make_bucket_fn(
                    list(node.partition_exprs), node.partition_domains, n,
                    jit=True)
                self._wave_fns[bucket_key] = bucket_fn
            buckets: List[List] = [[] for _ in range(n)]
            try:
                for p in stream.drain():
                    self._route_to_buckets(p, bucket_fn(p), buckets)
            except BaseException:
                ex.abort()
                raise
            finally:
                ex.join()
            return self._window_over_buckets(node, buckets)

    @staticmethod
    def _route_to_buckets(page: Page, bids, buckets: List[List]) -> None:
        """Append each bucket's (columns, valids, rows) slice of
        ``page`` — live rows only, hash-routed like the partitioned
        exchange write (PartitionedOutputOperator's host twin)."""
        bids_np = np.asarray(bids)
        mask = np.asarray(page.row_mask)
        datas = [np.asarray(b.data) for b in page.blocks]
        valids = [np.asarray(b.valid) for b in page.blocks]
        for k in range(len(buckets)):
            idx = np.nonzero(mask & (bids_np == k))[0]
            if idx.size:
                buckets[k].append(([d[idx] for d in datas],
                                   [v[idx] for v in valids], idx.size))

    def _window_over_buckets(self, node, buckets: List[List]) -> Page:
        """One shard_map'd window program over the stacked per-device
        bucket pages (each device holds complete partitions)."""
        from presto_tpu.exec.local import bucket_capacity
        from presto_tpu.obs import current_timeline

        src_channels = node.source.channels
        rows = [sum(r for _, _, r in parts) for parts in buckets]
        tl = current_timeline()
        if tl is not None:
            # per-partition row counts: the doctor's skew evidence
            tl.extend("partition_rows", "dist:window", rows)
        cap = bucket_capacity(max(max(rows), 1))
        # empty buckets mirror a non-empty bucket's column shapes/dtypes
        # (multi-dim blocks, e.g. long-decimal limbs, must stack evenly)
        ref = {}
        for parts in buckets:
            for p in parts:
                for i, d in enumerate(p[0]):
                    ref.setdefault(i, (d.shape[1:], d.dtype))
                break
        pages = []
        for parts in buckets:
            blocks = []
            for i, ch in enumerate(src_channels):
                if parts:
                    data = np.concatenate([p[0][i] for p in parts])
                    valid = np.concatenate([p[1][i] for p in parts])
                else:
                    shape, dtype = ref.get(i, ((), ch.type.np_dtype))
                    data = np.zeros((0,) + shape, dtype=dtype)
                    valid = np.zeros(0, np.bool_)
                pad = cap - data.shape[0]
                if pad > 0:
                    data = np.concatenate(
                        [data, np.zeros((pad,) + data.shape[1:], data.dtype)])
                    valid = np.concatenate([valid, np.zeros(pad, np.bool_)])
                blocks.append(Block(data, valid, ch.type, ch.dictionary))
            nlive = sum(r for _, _, r in parts)
            mask = np.zeros(cap, np.bool_)
            mask[:nlive] = True
            pages.append(Page(tuple(blocks), mask))
        stacked = _stack_pages(pages)

        fn_key = (node, "window", cap)
        win_fn = self._wave_fns.get(fn_key)
        if win_fn is None:
            from presto_tpu.ops.window import window_page

            partition_exprs = list(node.partition_exprs)
            order_exprs = list(node.order_exprs)
            ascending = list(node.ascending)
            funcs = list(node.funcs)
            pd = node.partition_domains
            mesh, axis = self.mesh, self.axis

            def per_device_window(page1):
                return _unsqueeze(window_page(
                    _squeeze(page1), partition_exprs, order_exprs,
                    ascending, funcs, partition_domains=pd))

            win_fn = jax.jit(
                shard_map(per_device_window, mesh=mesh, in_specs=P(axis),
                          out_specs=P(axis)))
            self._wave_fns[fn_key] = win_fn

        sharding = NamedSharding(self.mesh, P(self.axis))
        out = win_fn(jax.device_put(stacked, sharding))
        host_pages = _unstack_pages(jax.device_get(out), node.channels)
        return concat_pages_host(host_pages)

    def run_union_stage(self, node) -> Page:
        """UNION ALL as producer stages draining into ONE exchange: on
        a single mesh the legs' waves run back to back (the devices are
        shared), but their pages stream through the exchange so the
        consumer-side dictionary-offset mapping and concat overlap
        production, and the multihost tier runs the same shape with
        truly concurrent legs."""
        from presto_tpu.obs import span
        from presto_tpu.parallel.fragment import (
            is_agg_stage, remap_union_leg_page,
        )
        from presto_tpu.parallel.streams import page_nbytes

        chans = node.channels
        offsets = node.code_offsets
        with span("dist_stage:union", cat="exchange"):
            ex = self._exchange("union", "dist:union")
            stream = ex.stream()

            def produce(st):
                for k, leg in enumerate(node.inputs):
                    put = (lambda kk: lambda p: st.put(
                        (kk, p), nbytes=page_nbytes(p)))(k)
                    if is_agg_stage(leg, self.min_stage_rows):
                        put(self.run_aggregation_stage(leg))
                    else:
                        self._produce_chain_into(leg, put)

            ex.run(stream, produce)
            out: List[Page] = []
            try:
                for k, p in stream.drain():
                    out.append(remap_union_leg_page(p, offsets[k], chans))
            except BaseException:
                ex.abort()
                raise
            finally:
                ex.join()
            if not out:
                return Page.empty([c.type for c in chans], 1)
            return concat_pages_host(out)

    # ------------------------------------------------------------------
    def run_aggregation_stage(self, agg: AggregationNode) -> Page:
        """Distributed scan->chain->partial agg->exchange->final merge
        with group-overflow detection: every shard_map'd stage returns
        its live-group count (and the exchange its bucket fill); the
        host checks them and retries the stage with doubled max_groups,
        exactly as LocalRunner._check_overflow does locally (reference
        rehash: MultiChannelGroupByHash.java:138-145 tryRehash)."""
        if any(a.fn == "evaluate_classifier_predictions" for a in agg.aggs):
            # host-finalized string output: only the local runner
            # formats it after the final merge
            raise DistributedUnsupported(
                "evaluate_classifier_predictions is local-only")
        from presto_tpu.obs import span

        with span("dist_stage:aggregation", cat="exchange"):
            while True:
                try:
                    return self._run_aggregation_stage_once(agg)
                except GroupCapacityExceeded:
                    continue  # _mg_overrides updated; re-execute

    def _overflow(self, agg: AggregationNode, mg: int) -> None:
        if mg >= MAX_AGG_GROUPS:
            raise RuntimeError(
                f"distributed aggregation exceeded {MAX_AGG_GROUPS} groups per device"
            )
        self._mg_overrides[agg] = mg * 2
        self._evict_stage_fns(agg)
        raise GroupCapacityExceeded(mg * 2)

    def _evict_stage_fns(self, agg) -> None:
        """Drop compiled programs superseded by a capacity bump (their
        old (agg, mg, sig) keys are unreachable and pin executables)."""
        self._wave_fns = {k: v for k, v in self._wave_fns.items() if k[0] is not agg}
        self._final_fns = {k: v for k, v in self._final_fns.items() if k[0] is not agg}

    def _verify_checks(
        self, agg, ctx: "_ChainCtx", wave_checks, mg: int, check_groups: bool
    ) -> None:
        """Host-side verification of the wave programs' counters:
        exchange bucket fills, expanding-join totals, and live group
        counts.  Any exceeded capacity updates its config and raises
        GroupCapacityExceeded so the stage re-runs (counts are true
        totals, so one retry per knob suffices)."""
        if not wave_checks:
            return
        peaks: Dict[str, int] = {}
        for cks in wave_checks:
            for name, arr in cks.items():
                v = int(np.asarray(jax.device_get(arr)).max())
                peaks[name] = max(peaks.get(name, 0), v)
        bumped = False
        for name, jnode, kind in ctx.check_meta:
            peak = peaks.get(name, 0)
            cfg = self._join_cfg[jnode]
            if kind == "fill" and peak > cfg["bucket_cap"]:
                cfg["bucket_cap"] = 1 << (peak - 1).bit_length()
                bumped = True
            elif kind == "expand" and peak > cfg["out_cap"]:
                cfg["out_cap"] = 1 << (peak - 1).bit_length()
                bumped = True
        if check_groups and peaks.get("groups", 0) >= mg:
            self._overflow(agg, mg)  # raises
        if bumped:
            self._evict_stage_fns(agg)
            raise GroupCapacityExceeded(0)

    # ------------------------------------------------------------------
    # distributed chain compilation (joins distribute per fragmenter)
    # ------------------------------------------------------------------
    def _dist_chain_leaf(self, node: PlanNode) -> PlanNode:
        """Chain leaf for the distributed tier: descends through ALL
        joins' probe sides (expanding joins run in-program here, unlike
        the local chain)."""
        from presto_tpu.planner.plan import CrossSingleNode, JoinNode

        if isinstance(node, (FilterNode, ProjectNode)):
            return self._dist_chain_leaf(node.source)
        if isinstance(node, AggregationNode) and node.step == "partial":
            return self._dist_chain_leaf(node.source)
        if isinstance(node, CrossSingleNode):
            return self._dist_chain_leaf(node.left)
        if isinstance(node, JoinNode):
            return self._dist_chain_leaf(node.left)
        return node

    def _join_mode(self, jnode) -> str:
        """The fragmenter's broadcast-vs-repartition decision (it also
        owns the downgrade for non-chainable build sides, so EXPLAIN
        rendering and execution always agree)."""
        from presto_tpu.parallel.fragment import decide_join_distribution

        mode, _ = decide_join_distribution(
            jnode, self.broadcast_threshold, catalog=self.catalog,
            forced=self.join_distribution_type,
            allow_colocated=self.allow_colocated,
        )
        return mode

    def _join_cfg_for(self, jnode, cap: int) -> Dict[str, int]:
        """Static capacities for a partitioned/expanding join, grown by
        the check-and-retry protocol."""
        from presto_tpu.exec.local import bucket_capacity

        cfg = self._join_cfg.setdefault(jnode, {})
        n = self.n
        # bucket/out capacities ride the shared pow2/64K shape ladder:
        # raw 2*cap//n guesses are data-dependent (split row counts), so
        # every distinct table size compiled its own exchange + probe
        # programs — canonicalized caps let the registry hit instead
        cfg.setdefault("bucket_cap",
                       bucket_capacity(max(2 * cap // max(n, 1), 1024)))
        cfg.setdefault("out_cap", bucket_capacity(max(2 * cap, 4096)))
        cfg.setdefault("build_bucket_cap", 0)  # lazily set from build cap
        return cfg

    def _build_dist_stage(self, node: PlanNode, ctx: "_ChainCtx"):
        """fn(page, consts) -> (page, checks): the distributed analog of
        ``exec/chain.py``'s stages (ROADMAP D9).  ``checks`` maps check
        names to scalar counts (exchange fills, expand totals) the host
        verifies."""
        from presto_tpu.ops.filter_project import filter_page, project_page
        from presto_tpu.planner.plan import CrossSingleNode, JoinNode

        if isinstance(node, FilterNode):
            inner = self._build_dist_stage(node.source, ctx)
            pred = node.predicate

            def f_filter(p, c):
                q, ch = inner(p, c)
                return filter_page(q, pred), ch

            return f_filter

        if isinstance(node, ProjectNode):
            inner = self._build_dist_stage(node.source, ctx)
            projections = list(node.projections)

            def f_project(p, c):
                q, ch = inner(p, c)
                return project_page(q, projections), ch

            return f_project

        if isinstance(node, AggregationNode) and node.step == "partial":
            inner = self._build_dist_stage(node.source, ctx)
            group_exprs = list(node.group_exprs)
            aggs = list(node.aggs)
            pmg = self._stage_runner._max_groups(node)
            pkd = node.key_domains

            def f_pagg(p, c):
                q, ch = inner(p, c)
                return (
                    grouped_aggregate(
                        q, group_exprs, aggs, pmg, key_domains=pkd, mode="partial"
                    ),
                    ch,
                )

            return f_pagg

        if isinstance(node, CrossSingleNode):
            from presto_tpu.exec.chain import cross_append_single

            inner = self._build_dist_stage(node.left, ctx)
            key = ctx.add_broadcast(node)

            def f_cross(p, c):
                q, ch = inner(p, c)
                return cross_append_single(q, c[key]), ch

            return f_cross

        if isinstance(node, JoinNode):
            from presto_tpu.exec.chain import is_streaming_join

            if node.kind == "full":
                # the unmatched-build tail needs cross-page (and
                # cross-device) match state; falls back to local
                raise DistributedUnsupported("full outer join")
            if node.use_index:
                # point-lookup builds don't wave-scan (IndexLoader role)
                raise DistributedUnsupported("index join")
            inner = self._build_dist_stage(node.left, ctx)
            mode = self._join_mode(node)
            left_keys = list(node.left_keys)
            kd = node.key_domains
            kind = node.kind
            ns = node.null_safe_keys
            na = getattr(node, "null_aware", False)
            build_output = list(range(len(node.right.channels)))
            streaming = is_streaming_join(node)
            cfg = self._join_cfg_for(node, ctx.cap)
            n, axis = self.n, self.axis

            if mode == "broadcast":
                key = ctx.add_broadcast(node)
                if streaming:

                    def f_bjoin(p, c):
                        q, ch = inner(p, c)
                        return (
                            probe_join(
                                c[key], q, left_keys, key_domains=kd,
                                kind=kind, build_output=build_output,
                                null_safe=ns, null_aware=na,
                            ),
                            ch,
                        )

                    return f_bjoin

                out_cap = cfg["out_cap"]
                expand_check = ctx.add_check(node, "expand")

                def f_bexpand(p, c):
                    q, ch = inner(p, c)
                    out, total = probe_expand(
                        c[key], q, left_keys, out_cap, key_domains=kd,
                        kind=kind, build_output=build_output, null_safe=ns,
                    )
                    return out, {**ch, expand_check: total.astype(jnp.int32)}

                return f_bexpand

            if mode == "colocated":
                # bucket-aligned sides: device d already holds build
                # bucket w*n+d when probing split w*n+d — NO exchange
                # on either side (colocated_join /
                # NodePartitioningManager bucket alignment)
                key = ctx.add_sharded(node)
                if streaming:

                    def f_cjoin(p, c):
                        q, ch = inner(p, c)
                        out = probe_join(
                            _squeeze(c[key]), q, left_keys, key_domains=kd,
                            kind=kind, build_output=build_output, null_safe=ns,
                            null_aware=na,
                        )
                        return out, ch

                    return f_cjoin

                out_cap = cfg["out_cap"]
                expand_check = ctx.add_check(node, "expand")

                def f_cexpand(p, c):
                    q, ch = inner(p, c)
                    out, total = probe_expand(
                        _squeeze(c[key]), q, left_keys, out_cap, key_domains=kd,
                        kind=kind, build_output=build_output, null_safe=ns,
                    )
                    return out, {**ch, expand_check: total.astype(jnp.int32)}

                return f_cexpand

            # partitioned (repartitioned join): exchange probe rows on
            # the join key, probe the local build shard
            key = ctx.add_sharded(node)
            bucket_cap = cfg["bucket_cap"]
            fill_check = ctx.add_check(node, "fill")
            if streaming:

                def f_pjoin(p, c):
                    q, ch = inner(p, c)
                    t = partition_targets(q, left_keys, n, kd)
                    bucketized, fill = partition_for_exchange(q, t, n, bucket_cap)
                    ex = exchange_page(bucketized, axis)
                    out = probe_join(
                        _squeeze(c[key]), ex, left_keys, key_domains=kd,
                        kind=kind, build_output=build_output, null_safe=ns,
                        null_aware=na,
                    )
                    return out, {**ch, fill_check: fill}

                return f_pjoin

            out_cap = cfg["out_cap"]
            expand_check = ctx.add_check(node, "expand")

            def f_pexpand(p, c):
                q, ch = inner(p, c)
                t = partition_targets(q, left_keys, n, kd)
                bucketized, fill = partition_for_exchange(q, t, n, bucket_cap)
                ex = exchange_page(bucketized, axis)
                out, total = probe_expand(
                    _squeeze(c[key]), ex, left_keys, out_cap, key_domains=kd,
                    kind=kind, build_output=build_output, null_safe=ns,
                )
                return out, {
                    **ch, fill_check: fill, expand_check: total.astype(jnp.int32),
                }

            return f_pexpand

        # chain leaf (scan): identity
        return lambda p, c: (p, {})

    def _stage_source(self, chain_root: PlanNode) -> "_StageSource":
        leaf = self._dist_chain_leaf(chain_root)
        if not isinstance(leaf, (TableScanNode, PrecomputedNode)):
            raise DistributedUnsupported(
                f"chain leaf is {type(leaf).__name__}, not a table scan "
                "or materialized stage output")
        return _StageSource(self, leaf)

    def _run_aggregation_stage_once(self, agg: AggregationNode) -> Page:
        n = self.n
        runner = self._stage_runner

        source = self._stage_source(agg.source)
        cap = source.cap

        ctx = _ChainCtx(cap)
        stage = self._build_dist_stage(agg.source, ctx)

        # broadcast builds replicate to every device (BroadcastOutputBuffer
        # semantics); partitioned builds shard by join-key hash
        consts_rep = {
            key: runner._materialize_build(j) for key, j in ctx.broadcast.items()
        }
        consts_shard = {
            key: (self._materialize_build_colocated(j)
                  if self._join_mode(j) == "colocated"
                  else self._materialize_build_sharded(j))
            for key, j in ctx.sharded.items()
        }

        mg = self._mg_overrides.get(agg) or runner._max_groups(agg)
        # exact capacity (key-domain product fits mg) cannot truncate
        check = bool(agg.group_exprs) and not runner._exact_capacity(agg, mg)
        group_exprs = list(agg.group_exprs)
        aggs = list(agg.aggs)
        nk = len(group_exprs)
        kd = agg.key_domains
        partial_channels = AggregationNode(
            source=agg.source, group_exprs=group_exprs, group_names=agg.group_names,
            aggs=aggs, agg_names=agg.agg_names, step="partial",
        ).channels

        mesh, axis = self.mesh, self.axis

        def per_device_wave(page1, acc1, consts_r, consts_s):
            page = _squeeze(page1)
            acc = _squeeze(acc1)
            p, checks = stage(page, {**consts_r, **consts_s})
            part, c1 = grouped_aggregate(
                p, group_exprs, aggs, mg, key_domains=kd, mode="partial",
                return_count=True,
            )
            cand = concat_pages_device([acc, part])
            acc2, c2 = merge_aggregate(
                cand, nk, aggs, mg, key_domains=kd, mode="partial",
                return_count=True,
            )
            checks = dict(checks)
            checks["groups"] = jnp.maximum(c1, c2)
            return _unsqueeze(acc2), {k: v[None] for k, v in checks.items()}

        fn_key = (agg, mg, ctx.sig(self._join_cfg))
        wave_fn = self._wave_fns.get(fn_key)
        if wave_fn is None:
            check_specs = {name: P(axis) for name in ctx.checks}
            check_specs["groups"] = P(axis)
            wave_fn = jax.jit(
                shard_map(
                    per_device_wave, mesh=mesh,
                    in_specs=(
                        P(axis), P(axis), P(),
                        {k: P(axis) for k in consts_shard},
                    ),
                    out_specs=(P(axis), check_specs),
                )
            )
            self._wave_fns[fn_key] = wave_fn

        # ---- split scheduling: device d takes split w*n + d ----------
        sharding = NamedSharding(mesh, P(axis))

        acc = self._initial_acc(partial_channels, mg, n, sharding)
        wave_checks = []
        for stacked in self._wave_iter(source, sharding):
            acc, cks = wave_fn(stacked, acc, consts_rep, consts_shard)
            wave_checks.append(cks)
        self._verify_checks(agg, ctx, wave_checks, mg, check)

        # ---- exchange + final merge ----------------------------------
        if nk == 0:
            host_pages = _unstack_pages(jax.device_get(acc), partial_channels)
            cand = concat_pages_host(host_pages)
            return merge_aggregate(cand, 0, aggs, 1, key_domains=kd, mode="single")

        key_refs = [
            ColumnRef(type=partial_channels[i].type, index=i) for i in range(nk)
        ]

        def per_device_final(acc1):
            acc_l = _squeeze(acc1)
            target = partition_targets(acc_l, key_refs, n, kd)
            bucketized, fill = partition_for_exchange(acc_l, target, n, bucket_cap=mg)
            ex = exchange_page(bucketized, axis)
            merged, cnt = merge_aggregate(
                ex, nk, aggs, mg, key_domains=kd, mode="single", return_count=True
            )
            return _unsqueeze(merged), jnp.maximum(fill, cnt)[None]

        final_fn = self._final_fns.get((agg, mg))
        if final_fn is None:
            final_fn = jax.jit(
                shard_map(
                    per_device_final, mesh=mesh, in_specs=P(axis),
                    out_specs=(P(axis), P(axis)),
                )
            )
            self._final_fns[(agg, mg)] = final_fn
        out, fills = final_fn(acc)
        if check and int(np.asarray(jax.device_get(fills)).max()) >= mg:
            # a bucket overfilled in the exchange, or the post-exchange
            # merge saw >= mg distinct groups on some device
            self._overflow(agg, mg)
        out_channels = agg.channels
        host_pages = _unstack_pages(jax.device_get(out), out_channels)
        return concat_pages_host(host_pages)

    # ------------------------------------------------------------------
    def _stacked_wave(self, conn, leaf: TableScanNode, col_idx, w: int, cap: int) -> Page:
        """Host-assemble wave ``w``'s one-split-per-device stacked page
        (device d takes split w*n + d; missing splits pad empty)."""
        n = self.n
        table = leaf.handle.table
        n_splits = leaf.handle.num_splits
        pages = []
        for d in range(n):
            s = w * n + d
            if s < n_splits:
                pg = conn.page_for_split(table, s, capacity=cap)
                pg = Page(tuple(pg.blocks[i] for i in col_idx), pg.row_mask)
            else:
                pg = Page.empty([leaf.handle.columns[i].type for i in col_idx], cap)
                pg = Page(
                    tuple(
                        Block(b.data, b.valid, b.type, leaf.handle.columns[i].dictionary)
                        for b, i in zip(pg.blocks, col_idx)
                    ),
                    pg.row_mask,
                )
            pages.append(pg)
        return _stack_pages(pages)

    # ------------------------------------------------------------------
    # sharded (repartitioned) join builds
    # ------------------------------------------------------------------
    def _materialize_build_colocated(self, jnode) -> JoinBuild:
        """Build side of a colocated join: device d wave-scans its OWN
        build splits (the same w*n+d placement the probe leaf uses, so
        bucket b always lands where probe bucket b executes) — no
        exchange at all.  Reference: colocated joins over
        ConnectorNodePartitioningProvider bucketed tables."""
        key = (jnode, "colocated")
        cached = self._sharded_builds.get(key)
        if cached is not None:
            return cached
        n, mesh, axis = self.n, self.mesh, self.axis
        runner = self._stage_runner
        chain_r = lower_chain(jnode.right, max_groups=runner._max_groups,
                              compact_k=0)
        leaf_r, stage_r = chain_r.leaf, chain_r.fn()
        conn_r = self.catalog.connector(leaf_r.handle.connector_name)
        cap_r = self._split_capacity(conn_r, leaf_r.handle.table)
        consts_r = {
            f"build_{i}": runner._materialize_build(j)
            for i, j in enumerate(chain_r.joins)
        }
        right_keys = list(jnode.right_keys)
        kd = jnode.key_domains

        def bw(page1, crep):
            return _unsqueeze(stage_r(_squeeze(page1), crep))

        bw_fn = jax.jit(
            shard_map(bw, mesh=mesh, in_specs=(P(axis), P()),
                          out_specs=P(axis))
        )
        sharding = NamedSharding(mesh, P(axis))
        col_idx = list(leaf_r.columns)
        received: List[Page] = []
        waves = math.ceil(leaf_r.handle.num_splits / n)
        for w in range(waves):
            stacked = jax.device_put(
                self._stacked_wave(conn_r, leaf_r, col_idx, w, cap_r), sharding
            )
            received.append(bw_fn(stacked, consts_r))

        if len(received) == 1:
            big = received[0]
        else:
            b0 = received[0]
            big = Page(
                tuple(
                    Block(
                        jnp.concatenate([r.blocks[i].data for r in received], axis=1),
                        jnp.concatenate([r.blocks[i].valid for r in received], axis=1),
                        b.type,
                        b.dictionary,
                    )
                    for i, b in enumerate(b0.blocks)
                ),
                jnp.concatenate([r.row_mask for r in received], axis=1),
            )
        ns = getattr(jnode, "null_safe_keys", False)
        bj_fn = jax.jit(
            shard_map(
                lambda pg1: _unsqueeze(
                    build_join(_squeeze(pg1), right_keys, key_domains=kd,
                               null_safe=ns)
                ),
                mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            )
        )
        build = bj_fn(big)
        self._sharded_builds[key] = build
        return build

    def _materialize_build_sharded(self, jnode) -> JoinBuild:
        """Build side of a repartitioned join: wave-scan the build
        chain over the mesh, hash-exchange rows on the join key, then
        build one sorted JoinBuild per device over its key partition.
        Device p ends up holding exactly the build rows with
        hash(key) % n == p — the PartitionedLookupSourceFactory analog
        with the shuffle collapsed into ``all_to_all``."""
        leaf_r = chain_leaf(jnode.right)
        conn_r = self.catalog.connector(leaf_r.handle.connector_name)
        cap_r = self._split_capacity(conn_r, leaf_r.handle.table)
        cfg = self._join_cfg.setdefault(jnode, {})
        if not cfg.get("build_bucket_cap"):
            from presto_tpu.exec.local import bucket_capacity

            cfg["build_bucket_cap"] = bucket_capacity(
                max(2 * cap_r // max(self.n, 1), 1024))
        while True:
            key = (jnode, cfg["build_bucket_cap"])
            cached = self._sharded_builds.get(key)
            if cached is not None:
                return cached
            try:
                build = self._materialize_build_sharded_once(
                    jnode, leaf_r, conn_r, cap_r, cfg["build_bucket_cap"]
                )
                self._sharded_builds[key] = build
                return build
            except _BuildOverflow as e:
                # evict the undersized build (it pins device memory and
                # its key is unreachable once the cap grows)
                self._sharded_builds.pop(key, None)
                cfg["build_bucket_cap"] = e.needed

    def _materialize_build_sharded_once(
        self, jnode, leaf_r: TableScanNode, conn_r, cap_r: int, bcap: int
    ) -> JoinBuild:
        n, mesh, axis = self.n, self.mesh, self.axis
        runner = self._stage_runner
        chain_r = lower_chain(jnode.right, max_groups=runner._max_groups,
                              compact_k=0)
        stage_r = chain_r.fn()
        consts_r = {
            f"build_{i}": runner._materialize_build(j)
            for i, j in enumerate(chain_r.joins)
        }
        right_keys = list(jnode.right_keys)
        kd = jnode.key_domains

        def bw(page1, crep):
            page = _squeeze(page1)
            q = stage_r(page, crep)
            t = partition_targets(q, right_keys, n, kd)
            bucketized, fill = partition_for_exchange(q, t, n, bcap)
            ex = exchange_page(bucketized, axis)
            return _unsqueeze(ex), fill[None]

        bw_fn = jax.jit(
            shard_map(
                bw, mesh=mesh, in_specs=(P(axis), P()),
                out_specs=(P(axis), P(axis)),
            )
        )
        sharding = NamedSharding(mesh, P(axis))
        col_idx = list(leaf_r.columns)
        received: List[Page] = []
        fills = []
        waves = math.ceil(leaf_r.handle.num_splits / n)
        for w in range(waves):
            stacked = jax.device_put(
                self._stacked_wave(conn_r, leaf_r, col_idx, w, cap_r), sharding
            )
            rec, fill = bw_fn(stacked, consts_r)
            received.append(rec)
            fills.append(fill)
        from presto_tpu.obs import current_timeline

        fill_rows = [int(v) for f in fills
                     for v in np.asarray(jax.device_get(f)).reshape(-1)]
        peak = max(fill_rows)
        tl = current_timeline()
        if tl is not None:
            # per-device build fills after the repartitioning exchange —
            # the only host-visible per-partition counts of the sharded
            # join (the probe exchange lives inside the jitted program)
            tl.extend("partition_rows", "dist:join-build", fill_rows)
        if peak > bcap:
            raise _BuildOverflow(1 << (peak - 1).bit_length())

        if len(received) == 1:
            big = received[0]
        else:  # concat per device along the row axis (axis 0 is devices)
            b0 = received[0]
            big = Page(
                tuple(
                    Block(
                        jnp.concatenate([r.blocks[i].data for r in received], axis=1),
                        jnp.concatenate([r.blocks[i].valid for r in received], axis=1),
                        b.type,
                        b.dictionary,
                    )
                    for i, b in enumerate(b0.blocks)
                ),
                jnp.concatenate([r.row_mask for r in received], axis=1),
            )

        bj_fn = jax.jit(
            shard_map(
                lambda pg1: _unsqueeze(
                    build_join(_squeeze(pg1), right_keys, key_domains=kd)
                ),
                mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            )
        )
        return bj_fn(big)

    # ------------------------------------------------------------------
    def _split_capacity(self, conn, table: str) -> int:
        if hasattr(conn, "max_split_rows"):
            return int(conn.max_split_rows(table))
        # fall back: probe the first split's size, round up
        pg = conn.page_for_split(table, 0)
        return 1 << (max(pg.capacity - 1, 1)).bit_length()

    def _initial_acc(self, channels, mg: int, n: int, sharding) -> Page:
        blocks = []
        for ch in channels:
            shape = (n, mg)
            if ch.type.is_long_decimal:
                # widened decimal sum states ride the exchange as limb
                # matrices; all-zero limbs are the canonical combine
                # identity (ops/decimal128 layout)
                from presto_tpu.ops import decimal128 as d128

                shape += (d128.WIDE_LIMBS
                          if (ch.type.precision or 0) > 36 else 2,)
            blocks.append(
                Block(
                    jnp.zeros(shape, dtype=ch.type.np_dtype),
                    jnp.zeros((n, mg), dtype=jnp.bool_),
                    ch.type,
                    ch.dictionary,
                )
            )
        page = Page(tuple(blocks), jnp.zeros((n, mg), dtype=jnp.bool_))
        return jax.device_put(page, sharding)


def _stack_pages(pages: Sequence[Page]) -> Page:
    blocks = []
    for i in range(pages[0].num_blocks):
        b0 = pages[0].blocks[i]
        data = np.stack([np.asarray(p.blocks[i].data) for p in pages])
        valid = np.stack([np.asarray(p.blocks[i].valid) for p in pages])
        blocks.append(Block(data, valid, b0.type, b0.dictionary))
    mask = np.stack([np.asarray(p.row_mask) for p in pages])
    return Page(tuple(blocks), mask)


def _unstack_pages(stacked: Page, channels) -> List[Page]:
    n = np.asarray(stacked.row_mask).shape[0]
    out = []
    for d in range(n):
        blocks = tuple(
            Block(
                jnp.asarray(np.asarray(b.data)[d]),
                jnp.asarray(np.asarray(b.valid)[d]),
                ch.type,
                ch.dictionary,
            )
            for b, ch in zip(stacked.blocks, channels)
        )
        out.append(Page(blocks, jnp.asarray(np.asarray(stacked.row_mask)[d])))
    return out
