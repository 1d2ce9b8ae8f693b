"""Single-process pipeline executor.

Reference analog: the worker execution tier — ``operator/Driver.java:262``
(processFor loop moving Pages between operators), pipelines from
``planner/LocalExecutionPlanner.java:271``, and the in-process harness
``testing/LocalQueryRunner.java:584``.

TPU-first redesign: instead of thread-per-driver pulling one Page at a
time through virtual operator calls, the executor fuses every *streaming
chain* of a plan (scan -> filter -> project -> join-probe -> partial-agg)
into ONE jitted function Page -> Page, so XLA compiles the whole chain
into a single fused TPU program per split.  Pipeline breakers
(aggregation finalization, join build, sort) materialize, mirroring the
reference's pipeline boundaries at LocalExchange/HashBuilder points.

Data-dependent sizes (the big CPU/TPU impedance mismatch, SURVEY.md §7)
are handled with static capacities + live masks; expanding joins and
group-by overflow use count-check-and-retry with doubled capacity
(the analog of MultiChannelGroupByHash.tryRehash and the yielding
LookupJoinPageBuilder).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.catalog import Catalog
from presto_tpu.exec.chain import Chain, is_streaming_join, lower_chain
from presto_tpu.exec.programs import dispatched
from presto_tpu.obs.reads import host_read
from presto_tpu.ops.aggregate import grouped_aggregate, merge_aggregate
from presto_tpu.ops.join import JoinBuild, build_join, probe_expand, probe_join
from presto_tpu.ops.sort import limit_page, sort_page, sort_perm, topn_page
from presto_tpu.page import Block, Page
from presto_tpu.planner.plan import (
    AggregationNode,
    CrossSingleNode,
    GroupIdNode,
    JoinNode,
    LimitNode,
    OutputNode,
    PlanNode,
    PrecomputedNode,
    RemoteSourceNode,
    SortNode,
    TableScanNode,
    TopNNode,
    UnionNode,
    UnnestNode,
    ValuesNode,
    WindowNode,
)
from presto_tpu.types import Type


@dataclasses.dataclass
class MaterializedResult:
    """Host-side query result (testing/MaterializedResult.java analog)."""

    names: List[str]
    types: List[Type]
    rows: List[tuple]

    def __len__(self):
        return len(self.rows)

    def __iter__(self):
        return iter(self.rows)


def concat_pages_device(pages: Sequence[Page]) -> Page:
    """Concatenate pages column-wise on device (capacities may differ)."""
    if len(pages) == 1:
        return pages[0]
    blocks = []
    for i in range(pages[0].num_blocks):
        data = jnp.concatenate([p.blocks[i].data for p in pages])
        valid = jnp.concatenate([p.blocks[i].valid for p in pages])
        b0 = pages[0].blocks[i]
        blocks.append(Block(data, valid, b0.type, b0.dictionary))
    mask = jnp.concatenate([p.row_mask for p in pages])
    return Page(tuple(blocks), mask)


def bucket_capacity(n: int) -> int:
    """Shape-bucketed page capacity: next multiple of 64K for large
    pages, next power of two below that.  Pow2 alone doubles pages
    sitting just past a boundary (TPC-H generator splits land at
    ~1048576 +- 1200 rows, so pow2 sent a third of them to 2M — a 33%
    compute tax); 64K granularity keeps the waste <= 6.5% while still
    collapsing the data-dependent capacities that each cost a full
    XLA compile of the chain program.

    The 2048-row slack absorbs boundary straddle: generator split
    sizes scatter within ~1200 rows of the nominal split, so a bare
    ceil parked siblings of one scan in TWO adjacent buckets (1048576
    vs 1114112 measured at SF1) — one extra chain program per scan for
    0 rows of useful capacity.  Counts within slack below a boundary
    round up with their just-past-the-boundary siblings; exact
    multiples stay put so the function is idempotent.  The slack makes
    the map non-monotonic in a 2048-row band below each boundary
    (bounded extra padding, never insufficient capacity); scans avoid
    even that via the uniform-capacity pass in ``_source_pages``, which
    keeps a tail from overshooting the bucket its full-size siblings
    occupy."""
    n = int(n)
    if n >= (1 << 16):
        g = 1 << 16
        if n % g == 0:
            return n
        return ((n + 2048) // g + 1) * g
    return 1 << max(0, n - 1).bit_length()


def pad_page_to(page: Page, tgt: int) -> Page:
    """Pad a page with dead rows up to capacity ``tgt`` (no-op when
    already at least that large)."""
    cap = page.capacity
    if tgt <= cap or cap == 0:
        return page
    arrs, pm = _pad_arrays(
        tuple(b.data for b in page.blocks) + tuple(b.valid for b in page.blocks),
        page.row_mask, tgt - cap)
    nb = len(page.blocks)
    blocks = tuple(
        Block(arrs[i], arrs[nb + i], b.type, b.dictionary)
        for i, b in enumerate(page.blocks))
    return Page(blocks, pm)


def pad_page_pow2(page: Page) -> Page:
    """Pad a page with dead rows up to its bucketed capacity
    (bucket_capacity).  Scan splits otherwise carry data-dependent
    capacities (ragged last split, per-table row counts) and every
    distinct capacity costs a full XLA compile of the whole chain
    program — the dominant cold-start cost (19 of q3's 32 warmup
    compiles were one agg program re-traced per shape)."""
    return pad_page_to(page, bucket_capacity(page.capacity))


def _pad_arrays_impl(arrs, mask, pad):
    """One jitted program per (shapes, pad) signature — not one concat
    program per block — pads every column and the mask together."""
    out = tuple(
        jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) for a in arrs)
    return out, jnp.concatenate([mask, jnp.zeros((pad,), jnp.bool_)])


_pad_arrays = dispatched(
    "pad_arrays", jax.jit(_pad_arrays_impl, static_argnums=(2,)))


def slice_page(page: Page, n: int) -> Page:
    """First n physical rows (static slice — used after sorts where live
    rows are compacted to the front)."""
    blocks = tuple(
        Block(b.data[:n], b.valid[:n], b.type, b.dictionary) for b in page.blocks
    )
    return Page(blocks, page.row_mask[:n])


class QueryStats:
    """Per-plan-node execution stats (QueryStats/OperatorStats analog).
    Wall times are inclusive of upstream stages (chains are fused into
    one XLA program; exclusive per-operator timing would require
    breaking fusion).

    Keying: entries key on a STABLE structural node id — (structural
    signature, occurrence-within-plan) — not ``PlanNode`` object
    identity.  Keying by identity fragmented stats the moment a
    structurally identical node re-appeared (re-planned retries,
    rebuilt executors sharing registry programs): each object opened
    its own entry and EXPLAIN ANALYZE totals undercounted.  Twin nodes
    inside one plan (self-join scans) stay distinct through the
    occurrence index, assigned in deterministic walk order by
    :meth:`register_plan`."""

    def __init__(self):
        self.by_key: Dict[tuple, Dict[str, float]] = {}
        self._key_of: Dict[int, tuple] = {}
        # keyed nodes are pinned so their id() can never be recycled
        # onto a different node mid-lifetime
        self._pin: List[PlanNode] = []
        # record() runs on whichever thread iterates the page
        # generator; distributed roll-up merges from puller threads
        import threading

        self._lock = threading.Lock()

    @staticmethod
    def _sig(node: PlanNode):
        from presto_tpu.exec.programs import structural_digest

        return (type(node).__name__, structural_digest(node))

    def register_plan(self, root: PlanNode) -> None:
        """Assign keys for a whole tree in preorder walk order, so two
        structurally identical plans map node-for-node onto the SAME
        keys: stats recorded while executing a re-built plan land on
        the entries the original plan's annotations read."""
        for n, key in plan_node_keys(root):
            if id(n) not in self._key_of:
                self._key_of[id(n)] = key
                self._pin.append(n)

    def _key(self, node: PlanNode) -> tuple:
        k = self._key_of.get(id(node))
        if k is None:
            # lazily seen node (e.g. an injected partial-agg stage not
            # present in the registered tree): occurrence 0 of its
            # signature — structural twins merge, which is the point
            k = (self._sig(node), 0)
            self._key_of[id(node)] = k
            self._pin.append(node)
        return k

    def record(self, node: PlanNode, wall: float, rows: int,
               nbytes: int = 0) -> None:
        with self._lock:
            s = self.by_key.setdefault(
                self._key(node),
                {"invocations": 0, "rows": 0, "wall_s": 0.0, "bytes": 0})
            s["invocations"] += 1
            s["rows"] += rows
            s["wall_s"] += wall
            s["bytes"] += nbytes

    def annotation(self, node: PlanNode) -> str:
        s = self.by_key.get(self._key(node))
        if s is None or not s["invocations"]:
            return ""
        return (
            f"  [rows={s['rows']}, pages={s['invocations']}, "
            f"wall={s['wall_s'] * 1e3:.1f}ms]"
        )

    def actual_rows(self, node: PlanNode) -> Optional[int]:
        """Observed output rows for a node, or None when it never
        recorded (est-vs-actual rendering, history feed)."""
        s = self.by_key.get(self._key(node))
        if s is None or not s["invocations"]:
            return None
        return int(s["rows"])

    # -- distributed roll-up wire format ------------------------------------
    # Keys are stable across processes (structural_digest), so a
    # worker's by_key snapshot serializes as JSON and merges onto the
    # coordinator's entries by key alone — the OperatorStats →
    # TaskStats → QueryStats roll-up of the reference, flattened.
    def to_wire(self) -> list:
        with self._lock:
            return [
                {"node": sig[0], "digest": sig[1], "occ": occ,
                 "invocations": int(s["invocations"]),
                 "rows": int(s["rows"]), "wall_s": float(s["wall_s"]),
                 "bytes": int(s.get("bytes", 0))}
                for (sig, occ), s in self.by_key.items()
            ]

    def merge_wire(self, entries) -> None:
        with self._lock:
            for e in entries or ():
                key = ((str(e["node"]), str(e["digest"])), int(e["occ"]))
                s = self.by_key.setdefault(
                    key, {"invocations": 0, "rows": 0, "wall_s": 0.0,
                          "bytes": 0})
                s["invocations"] += int(e.get("invocations", 0))
                s["rows"] += int(e.get("rows", 0))
                s["wall_s"] += float(e.get("wall_s", 0.0))
                s["bytes"] += int(e.get("bytes", 0))


def plan_node_keys(root: PlanNode):
    """``[(node, ((type name, digest), occurrence))]`` for a whole plan
    tree in deterministic preorder — THE shared key walk: QueryStats
    registration, bind-time estimate capture, and the history provider
    all key through this one function, so estimates and actuals share a
    key space by construction."""
    counts: Dict[tuple, int] = {}
    out = []
    stack = [root]
    while stack:
        n = stack.pop()
        sig = QueryStats._sig(n)
        occ = counts.get(sig, 0)
        counts[sig] = occ + 1
        out.append((n, (sig, occ)))
        stack.extend(reversed(n.sources))
    return out


# Ceiling for capacity-doubling retries, shared by the local, mesh
# (parallel/dist.py) and multi-host (parallel/multihost.py) runners.
MAX_AGG_GROUPS = 1 << 26

# Capacity beyond which aggregation stops doubling in place and
# switches to host-RAM partitioned (spill) execution instead —
# the MemoryRevokingScheduler threshold analog.
SPILL_GROUP_THRESHOLD = 1 << 22


class GroupCapacityExceeded(Exception):
    """An aggregation saw more groups than its static capacity; the
    runner retries the query with a doubled max_groups (the analog of
    MultiChannelGroupByHash.java:138 tryRehash), or switches to the
    partitioned spill path past SPILL_GROUP_THRESHOLD."""

    def __init__(self, needed: int, node=None):
        self.needed = needed
        self.node = node


class CompactionMissed(Exception):
    """A page of a compacting chain held more live rows than its small
    page (``_chain_pages``): the pages the chain gave are not to be
    used.  The chain is marked, and the aggregation that was consuming
    it (``node`` is the chain's root, its partial step) starts again
    over the chain that does not compact (``_run_aggregation``)."""

    def __init__(self, node):
        self.node = node


def _split_pruned(constraints, stats) -> bool:
    """True if split min/max stats prove no row can satisfy ALL the
    pushed-down conjuncts (ORC stripe-stats pruning role), via the
    TupleDomain pushdown language (spi/predicate/TupleDomain.java
    analog; closed-interval form is conservative for strict bounds)."""
    from presto_tpu.predicate import TupleDomain

    td = TupleDomain.from_constraints(constraints)
    return td.is_none or not td.overlaps_split_stats(stats)


def _named(f, name: str):
    """``f`` under ``name``.  XLA calls a program ``jit_<the function's
    name>``, which is what a device trace shows and part of the
    persistent compile cache's key (scopes alone are not: jax strips
    metadata before it hashes).  So the name must be a function of the
    program's kind and structure only — nothing from ``ir_signature``,
    ``id`` or ``hash``, which differ between processes and would make
    every start a cold one."""
    f.__name__ = f.__qualname__ = name
    return f


# this thread's counts, never reset: .compacted and .fallback pages of
# compacting chains (``compact_counts``); .expand_retries and
# .expanded_rows of expanding probes (``expand_counts``);
# .arith_checked and .arith_proven sites of the chains lowered
# (``arith_counts``); .chain_probes of them (``chain_probes``).  A
# query's counts are the differences across it, like
# ``obs.reads.host_reads``
_COUNTS = threading.local()


def compact_counts() -> Tuple[int, int]:
    """(pages of chains that ran compacted, pages of chains that had
    to run again whole) on this thread so far (``_chain_pages``)."""
    return (getattr(_COUNTS, "compacted", 0),
            getattr(_COUNTS, "fallback", 0))


def expand_counts() -> Tuple[int, int]:
    """(expanding probes that ran a second time at a larger capacity,
    rows expanding probes emitted: the ``total`` each read) on this
    thread so far (``_probe_with_retry``)."""
    return (getattr(_COUNTS, "expand_retries", 0),
            getattr(_COUNTS, "expanded_rows", 0))


def arith_counts() -> Tuple[int, int]:
    """(checked, proven): the guarded arithmetic sites and limb sums
    that the chains this thread has run so far compiled with and
    without their runtime guard (``Chain.arith_counts``, known on the
    host when ``_chain_pages`` lowers a chain, whether or not its
    program was already compiled)."""
    return (getattr(_COUNTS, "arith_checked", 0),
            getattr(_COUNTS, "arith_proven", 0))


def chain_probes() -> int:
    """Probes of the chains this thread has run so far: a chain that
    probes four builds in a row over each page counts four, whatever
    its pages (``Chain.probes``, known on the host when ``_chain_pages``
    lowers a chain, like ``arith_counts``)."""
    return getattr(_COUNTS, "chain_probes", 0)


def _extent_live(mask):
    """(highest live index + 1, live count) of a row mask, as one
    2-element device array so the host pays a single transfer."""
    idx = jnp.arange(mask.shape[0], dtype=jnp.int32)
    extent = jnp.max(jnp.where(mask, idx, -1)) + 1
    return jnp.stack([extent, jnp.sum(mask.astype(jnp.int32))])


# the program keeps its name, jit__extent_live
_extent_live = dispatched("extent_live", jax.jit(_extent_live))


class _AggFoldTower:
    """Binary-counter (LSM-style) fold of partial aggregation pages.

    The round-4 running fold concatenated every partial page onto a
    full-capacity accumulator and re-sorted ~2*max_groups keys per
    split; at SF10 that made Q3's aggregation tail ~57x slower for 10x
    data.  Two fixes compose here:

    - each incoming partial page is sliced to the power-of-two bucket
      just above its live extent (sort-path partials arrive
      front-compacted, and extent-based slicing is safe even for the
      packed-direct layout), so merge sizes track the data rather than
      the planner's conservative ``max_groups``; and
    - pages merge in a binary-counter tower — one slot per capacity,
      a carry merges equal-capacity pages — so every group takes part
      in O(log splits) merges instead of one full-capacity re-sort per
      split.  This is the sorted-run analog of the reference's
      incremental hash builder, which pays O(1) hash updates per row
      (operator/aggregation/builder/InMemoryHashAggregationBuilder.java,
      MultiChannelGroupByHash.java:138-145).

    Truncation: tower merges are UNCLAMPED — capacities follow the live
    data past ``max_groups``, so the merged result is exact no matter
    how conservative the planner's capacity guess was.  The one place
    truncation can still happen is INSIDE the jitted chain's per-split
    partial aggregation (grouped_aggregate at static ``max_groups``);
    an input page arriving full (live >= max_groups) records
    ``suspect_truncation`` and the caller re-plans with a capacity
    jumped to the observed live total (one retry, not a doubling
    ladder — MultiChannelGroupByHash.java:138 rehashes incrementally;
    this is the static-shape analog).
    """

    # floor of the slice/merge capacity ladder.  4096 starts typical
    # per-split partials (a few thousand live groups) at ONE level, so
    # the binary counter compiles log2(splits)-1 merge programs instead
    # of one more; merging <=4096 rows is noise on the VPU either way
    MIN_CAP = 1 << 12

    def __init__(self, runner, node, fns, mg, account=True):
        self.runner = runner
        self.node = node
        self.mg = mg
        self.account = account
        self.levels: Dict[int, tuple] = {}  # capacity -> (page, live, tag)
        # a full input page means the chain's static-capacity partial
        # may have dropped groups; the total live count sizes the retry
        self.suspect_truncation = False
        self.live_total = 0
        self.fold, self.final = fns

    @staticmethod
    def programs(runner, num_keys, aggs, kd):
        """The (fold, final) programs every tower of one aggregation
        shares: fetched once per operator, not once per tower (the
        spilled path builds one per bucket and retry)."""
        def fold(pages, out_cap):
            with jax.named_scope("op:Aggregation"):
                return merge_aggregate(
                    concat_pages_device(list(pages)), num_keys,
                    list(aggs), out_cap, key_domains=kd, mode="partial",
                    return_count=True)

        def final(pages, out_cap):
            with jax.named_scope("op:Aggregation"):
                return merge_aggregate(
                    concat_pages_device(list(pages)), num_keys,
                    list(aggs), out_cap, key_domains=kd, mode="single")

        _named(fold, "agg_tower_fold")
        _named(final, "agg_tower_final")
        sig = (num_keys, tuple(aggs), tuple(kd or ()))
        return tuple(
            runner._program(
                kind, sig,
                lambda f=f: jax.jit(f, static_argnames=("out_cap",))
                if runner.jit else f)
            for kind, f in (("agg_tower_fold", fold),
                            ("agg_tower_final", final)))

    def _cap(self, n: int) -> int:
        """Pow2 capacity bound — never clamped to max_groups: tower
        merges follow the live data, so results are exact past the
        planner's capacity guess."""
        return max(self.MIN_CAP, 1 << max(0, int(n) - 1).bit_length())

    _slice_cap = _cap

    def _reserve(self, page):
        if not self.account or self.runner._mem is None:
            return None
        from presto_tpu.memory import page_bytes

        return self.runner._mem.reserve(
            f"agg_accumulator@{id(self.node)}", page_bytes(page))

    def add(self, page: Page) -> None:
        el = host_read(_extent_live(page.row_mask), "extent")
        extent, live = int(el[0]), int(el[1])
        self.live_total += live
        if live >= self.mg:
            self.suspect_truncation = True
        cap = self._slice_cap(extent)
        if page.capacity > cap:
            page = slice_page(page, cap)
        mem = self.runner._mem if self.account else None
        tag = self._reserve(page)
        cap = page.capacity
        while cap in self.levels:
            o_page, o_live, o_tag = self.levels.pop(cap)
            # shape-determined merge capacity: the binary counter only
            # merges equal-capacity pages, so 2*cap always fits
            # live + o_live — a live-count-derived out_cap flip-flopped
            # between cap and 2*cap, compiling two programs per level
            out_cap = 2 * cap
            page, cnt = self.fold([o_page, page], out_cap=out_cap)
            live = min(int(host_read(cnt, "fold_count")), out_cap)
            if mem is not None:
                mem.free(tag)
                mem.free(o_tag)
            tag = self._reserve(page)
            cap = page.capacity
        self.levels[cap] = (page, live, tag)

    def release(self) -> None:
        """Drop every level and its reservation: the pages came from a
        try that is not to be used (``CompactionMissed``)."""
        mem = self.runner._mem if self.account else None
        for _, _, tag in self.levels.values():
            if mem is not None and tag is not None:
                mem.free(tag)
        self.levels.clear()

    def finish_single(self) -> Optional[Page]:
        """One mode='single' merge over the surviving level pages,
        largest first (deterministic program signature)."""
        if not self.levels:
            return None
        entries = sorted(self.levels.values(), key=lambda e: -e[0].capacity)
        pages = [e[0] for e in entries]
        out_cap = self._cap(sum(e[1] for e in entries))
        return self.final(pages, out_cap=out_cap)


def _probe_with_retry(probe_fn, build, page):
    """One expanding probe with the bucketed capacity retry shared by
    the in-HBM and spilled join paths (yielding LookupJoinPageBuilder
    analog). probe_fn(build, page, out_capacity) -> (page, total, ...).
    Retry capacities ride the same pow2/64K ladder as scan pages
    (bucket_capacity) so expansions that land near each other share one
    compiled probe program instead of one per observed match count."""
    cap = max(int(page.capacity), 1024)
    res = probe_fn(build, page, cap)
    total = int(host_read(res[1], "probe_total"))
    _COUNTS.expanded_rows = getattr(
        _COUNTS, "expanded_rows", 0) + total
    if total > cap:
        _COUNTS.expand_retries = getattr(
            _COUNTS, "expand_retries", 0) + 1
        res = probe_fn(build, page, bucket_capacity(total))
    return res


class LocalRunner:
    """Executes a plan tree against registered connectors.

    ``jit=False`` runs chains eagerly for debugging.
    """

    def __init__(self, catalog: Catalog, jit: bool = True, split_capacity: Optional[int] = None,
                 memory_pool=None, spill_partitions: int = 8, programs=None,
                 task_concurrency: Optional[int] = None,
                 task_prefetch: Optional[int] = None):
        from presto_tpu.exec.programs import (
            default_registry, enable_persistent_cache,
        )
        from presto_tpu.exec.tasks import (
            task_concurrency_default, task_prefetch_default,
        )
        from presto_tpu.ops.join import resolve_direct_join

        self.catalog = catalog
        self.jit = jit
        self.split_capacity = split_capacity
        # morsel-driven split scheduler knobs (exec/tasks.py): splits
        # in flight per pipeline (1 = the exact legacy serial path) and
        # prefetch depth.  None resolves the process default, which is
        # env/config-derived — resolved ONCE here, not per chain.
        self.task_concurrency = max(1, int(
            task_concurrency if task_concurrency
            else task_concurrency_default()))
        self.task_prefetch = max(0, int(
            task_prefetch if task_prefetch is not None and task_prefetch >= 0
            else task_prefetch_default()))
        # structural program registry (ExpressionCompiler-cache analog):
        # compiled callables keyed by kernel family + canonical IR +
        # baked-in parameters, shared process-wide unless injected
        self.programs = programs if programs is not None else default_registry()
        enable_persistent_cache()
        # env-dependent kernel choices resolve ONCE at construction —
        # not per join build (satellite of the registry PR)
        resolve_direct_join()
        # per-THREAD stats sink (property below): worker task threads
        # and concurrent coordinator queries share one runner, and a
        # shared sink would interleave two queries' actuals
        import threading as _threading

        self._stats_tls = _threading.local()
        # HBM accounting (memory/MemoryPool.java analog); None = untracked
        self.memory_pool = memory_pool
        # per-THREAD last-query peaks (properties below): concurrent
        # queries on one runner must not swap memory footprints — the
        # coordinator records last_peak_bytes into the admission
        # projection history, and a cross-query swap would make a light
        # statement inherit a heavy one's 8GB projection (and vice
        # versa, defeating the memory gate)
        import threading as _threading

        self._peaks_tls = _threading.local()
        # host-RAM spill fan-out when state exceeds the pool/threshold
        self.spill_partitions = spill_partitions
        # multi-producer ORDER BY: per-page sorts + order-preserving
        # merge (distributed_sort session property analog)
        self.merge_sort = True
        # per-THREAD query memory context: concurrent queries share one
        # runner (the coordinator runs each on its own thread), so the
        # active context must not be clobbered across threads
        import threading as _threading

        self._mem_tls = _threading.local()
        self._agg_overrides: Dict[PlanNode, int] = {}
        self._partial_nodes: Dict[PlanNode, AggregationNode] = {}
        # per-THREAD materialized join builds: device-resident state
        # that concurrent queries (and worker task threads) must not
        # share or clobber; dies with the thread
        self._builds_tls = _threading.local()
        # joins demoted out of fused chains because their build spilled
        self._force_expanding: set = set()
        # chains (by root) one of whose pages did not fit its compaction
        self._no_compact: set = set()
        # the partial aggregation whose chain the breaker on this
        # thread is about to consume, and can consume again: only its
        # chain may compact (``_run_aggregation_impl``)
        self._restartable_tls = _threading.local()
        # per-query split-scheduler stats (consumer-thread-local: the
        # scheduler's worker threads report through the shared stats
        # object, but the accumulator is owned by the query thread) and
        # the completed-query snapshot EXPLAIN ANALYZE prints
        self._task_stats_tls = _threading.local()
        self.last_task_stats: Dict[str, float] = {}
        # consume-once unordered-delivery grant: an order-insensitive
        # consumer (exact commutative aggregation fold) sets it just
        # before pulling a chain; the TOP-level chain takes completion-
        # order delivery, nested chains (join builds) stay ordered
        self._unordered_tls = _threading.local()
        # the evidence this thread's query proves its chains under:
        # (EvidenceContext, channel_values memo), ``_proving``
        self._evidence_tls = _threading.local()

    # ------------------------------------------------------------------
    def run(self, plan: PlanNode, query_id: Optional[str] = None) -> MaterializedResult:
        from presto_tpu.obs import span

        page = self.run_to_page(plan, query_id=query_id)
        # the result transfer is the last device sync of a local query:
        # the read itself is host_read:result, the span around it also
        # times compaction and the conversion to Python rows
        with span("device_get", cat="device"):
            rows = host_read(page, "result").compact_host().to_pylist()
        return MaterializedResult(
            names=plan.output_names,
            types=plan.output_types,
            rows=rows,
        )

    def _query_mem(self, query_id: Optional[str]):
        """Per-query memory-context ceremony shared by run_to_page and
        stream_pages: pool reservations tagged by the COORDINATOR's
        query id so the cluster memory manager can attribute + kill."""
        import contextlib

        @contextlib.contextmanager
        def ctx():
            from presto_tpu.exec.tasks import SchedulerStats

            self._task_stats_tls.stats = SchedulerStats()
            # per-query: predicted-interval memo keys on id(node), which
            # is only stable while this query's plan is alive
            self._range_pred_memo = {}
            if self.memory_pool is not None:
                from presto_tpu.memory import QueryMemoryContext
                import uuid

                self._mem = QueryMemoryContext(
                    self.memory_pool, query_id or uuid.uuid4().hex[:8])
            try:
                with self._proving():
                    yield
            finally:
                self.last_task_stats = self._task_stats.as_dict()
                if self._mem is not None:
                    self.last_peak_bytes = self._mem.peak
                    # per-site peak reservations (site strings embed the
                    # plan-node id) survive the context so EXPLAIN
                    # ANALYZE can attribute peak bytes per operator
                    self.last_site_peaks = dict(self._mem.site_peak)
                    self._mem.release_all()
                    self._mem = None

        return ctx()

    def run_to_page(self, plan: PlanNode, query_id: Optional[str] = None) -> Page:
        with self._query_mem(query_id):
            while True:
                try:
                    self._builds.clear()
                    return self._execute_to_page(plan)
                except GroupCapacityExceeded:
                    continue  # _agg_overrides updated; re-execute

    def stream_pages(self, plan: PlanNode, query_id: Optional[str] = None) -> Iterator[Page]:
        """Stream output pages with run_to_page's memory-context
        ceremony but no internal retry: GroupCapacityExceeded
        propagates so a caller that consumed partial output can restart
        from scratch (the scaled-writer ingest path)."""
        with self._query_mem(query_id):
            self._builds.clear()
            yield from self._pages(plan)

    @property
    def stats(self) -> Optional[QueryStats]:
        """Per-THREAD QueryStats sink: pages record on the thread that
        iterates the generator, and worker task quanta rebind this per
        step — a plain attribute would let concurrent queries (or two
        worker tasks) interleave actuals."""
        return getattr(self._stats_tls, "stats", None)

    @stats.setter
    def stats(self, value: Optional["QueryStats"]) -> None:
        self._stats_tls.stats = value

    @property
    def _builds(self) -> Dict[JoinNode, JoinBuild]:
        got = getattr(self._builds_tls, "builds", None)
        if got is None:
            got = {}
            self._builds_tls.builds = got
        return got

    @property
    def last_peak_bytes(self) -> int:
        """Peak reserved bytes of the last query completed ON THIS
        THREAD (EXPLAIN headers and the coordinator's admission
        projection both read the footprint of the query they just
        ran, never a concurrent one's)."""
        return getattr(self._peaks_tls, "peak", 0)

    @last_peak_bytes.setter
    def last_peak_bytes(self, value: int) -> None:
        self._peaks_tls.peak = value

    @property
    def last_site_peaks(self) -> Dict[str, int]:
        """Per-site peaks of the last query completed on this thread
        (EXPLAIN ANALYZE's per-operator memory source)."""
        got = getattr(self._peaks_tls, "sites", None)
        return got if got is not None else {}

    @last_site_peaks.setter
    def last_site_peaks(self, value: Dict[str, int]) -> None:
        self._peaks_tls.sites = value

    @property
    def _mem(self):
        return getattr(self._mem_tls, "ctx", None)

    @_mem.setter
    def _mem(self, value):
        self._mem_tls.ctx = value

    @property
    def _task_stats(self):
        from presto_tpu.exec.tasks import SchedulerStats

        got = getattr(self._task_stats_tls, "stats", None)
        if got is None:
            got = SchedulerStats()
            self._task_stats_tls.stats = got
        return got

    def _take_unordered(self) -> bool:
        """Pop the consume-once unordered-delivery grant (see
        ``_unordered_tls``)."""
        got = getattr(self._unordered_tls, "ok", False)
        if got:
            self._unordered_tls.ok = False
        return bool(got)

    def _account(self, what: str, page, node=None) -> None:
        """Charge a materialized device intermediate against the pool
        (operator-level LocalMemoryContext.setBytes analog). ``node``
        tags the reservation so spill fallbacks can attribute failures
        to their own plan node."""
        if self._mem is not None:
            from presto_tpu.memory import page_bytes

            if node is not None:
                what = f"{what}@{id(node)}"
            self._mem.reserve(what, page_bytes(page))

    def explain(self, plan: PlanNode) -> str:
        from presto_tpu.planner.plan import plan_tree_str

        return plan_tree_str(plan)

    def explain_with_stats(self, plan: PlanNode, stats: "QueryStats",
                           misestimate_factor: float = 8.0) -> str:
        from presto_tpu.obs.history import worst_estimate
        from presto_tpu.planner.plan import plan_tree_str

        text = plan_tree_str(plan, stats=stats, mem=self._mem_by_node(),
                             misestimate_factor=misestimate_factor)
        worst = worst_estimate(stats, getattr(plan, "_estimates", None))
        if worst is not None and worst["ratio"] >= misestimate_factor:
            text = (f"worst estimate: {worst['node']} "
                    f"est {worst['est']:.0f} rows / actual "
                    f"{worst['actual']} rows (x{worst['ratio']:.1f})\n"
                    + text)
        peak = getattr(self, "last_peak_bytes", 0)
        if peak:
            text = f"peak reserved memory: {peak / 1e6:.1f}MB\n" + text
        sched = self._scheduler_line()
        if sched:
            text = sched + "\n" + text
        return text

    def _scheduler_line(self) -> str:
        """One-line split-scheduler summary for EXPLAIN ANALYZE (empty
        when the last query ran no splits through a scan pipeline)."""
        ts = getattr(self, "last_task_stats", None) or {}
        if not ts.get("splits"):
            return ""
        total = ts["prefetch_hits"] + ts["prefetch_misses"]
        return (f"task scheduler: {ts['splits']} splits, "
                f"concurrency {ts['concurrency']}, "
                f"stall {ts['stall_s']:.3f}s, "
                f"prefetch hits {ts['prefetch_hits']}/{total}")

    def _mem_by_node(self) -> Dict[int, int]:
        """id(plan node) -> peak reserved bytes, recovered from the last
        query's tagged reservation sites (``what@<id(node)>`` — the tag
        convention of :meth:`_account` and the agg tower).  Sites for
        different allocation kinds on the same node sum; sites without a
        node id (scan pages, sort input) stay in the query-level peak
        header only."""
        import re as _re

        out: Dict[int, int] = {}
        for site, nbytes in getattr(self, "last_site_peaks", {}).items():
            m = _re.search(r"@(\d+)$", site)
            if m:
                nid = int(m.group(1))
                out[nid] = out.get(nid, 0) + nbytes
        return out

    # ------------------------------------------------------------------
    # EXPLAIN ANALYZE VERBOSE: exclusive per-operator attribution
    # ------------------------------------------------------------------
    def explain_analyze_verbose(self, plan: PlanNode) -> str:
        """Fused chains make normal EXPLAIN ANALYZE times inclusive of
        everything upstream.  VERBOSE mode re-executes every chain
        prefix-by-prefix — scan alone, scan+filter, scan+filter+probe,
        … — and reports the DELTAS as exclusive per-operator device
        time (the reference's per-operator OperatorStats, recovered by
        deliberately breaking fusion; the numbers cost extra runs and
        differ slightly from the fused program's true schedule)."""
        from presto_tpu.planner.plan import plan_tree_str

        stats = QueryStats()
        stats.register_plan(plan)
        self.stats = stats
        try:
            self.run(plan)
        finally:
            self.stats = None
        exclusive = self._exclusive_times(plan)
        text = plan_tree_str(plan, stats=stats, exclusive=exclusive,
                             mem=self._mem_by_node())
        peak = getattr(self, "last_peak_bytes", 0)
        if peak:
            text = f"peak reserved memory: {peak / 1e6:.1f}MB\n" + text
        reg = self.programs.stats()
        text = f"compiled XLA programs: {reg['programs']}\n" + text
        line = (f"program registry: {reg['callables']} callables, "
                f"{reg['programs']} compiled programs, "
                f"{reg['hits']} hits / {reg['misses']} misses, "
                f"compile {reg['compile_s']:.1f}s")
        if reg.get("dir"):
            line += (f", persistent cache hits {reg['persistent_hits']}"
                     f" ({reg['dir']})")
        text = line + "\n" + text
        sched = self._scheduler_line()
        if sched:
            text = sched + "\n" + text
        report = getattr(plan, "_optimizer_report", None)
        if report is not None:
            # "optimizer: N iterations, rule hits: ..." — which rules
            # shaped this plan (binder attaches the OptimizerStats)
            text = report.summary() + "\n" + text
        return text

    def _program(self, kind: str, sig, factory):
        """Compiled callable for (kind, structural signature) from the
        shared registry, the only program cache there is: identical
        operator shapes in other plans, queries and runners resolve to
        the same callable.  ``sig`` holds every value ``factory``'s
        callable bakes in, and a capacity retry or a demoted join
        changes it (``_max_groups``, ``_streaming``), so nothing is
        ever invalidated.  Ask once per operator per statement, not
        once per page."""
        return self.programs.get(kind, sig, factory, jit=self.jit)

    def _lower(self, node: PlanNode,
               compact_k: Optional[int] = None) -> Chain:
        """The streaming chain rooted at ``node``, as this runner
        stands: its demoted joins, its capacity retries, its missed
        compactions."""
        from presto_tpu.obs import span

        if node in self._no_compact:
            compact_k = 0
        proving = getattr(self._evidence_tls, "state", None) is not None
        # the evidence a proof reads (domains, split counts) is read
        # here, lazily, the first time a chain of the query asks
        with span("chain:lower", cat="operator"):
            return lower_chain(node, streaming=self._streaming,
                               max_groups=self._max_groups,
                               compact_k=compact_k,
                               intervals=self._intervals if proving else None)

    @contextlib.contextmanager
    def _proving(self):
        """The scope chains are proved in: one query on this thread.

        Inside, ``_intervals`` answers from one ``kernel_soundness.
        EvidenceContext`` and one memo (both key on ``id(node)``, which
        is only stable while the query's plan is alive), so a plan is
        walked once however many chains it lowers, every chain of the
        query sees a table in ONE state (domains and split count read
        together, the first time a chain asks), and ``_source_pages``
        runs each scan over the splits counted then.  Outside it (a
        worker's fragment pulled through ``_pages``) nothing is
        proved and every guard stays, as in the mesh and HTTP tiers."""
        from presto_tpu.analysis.kernel_soundness import EvidenceContext

        outer = getattr(self._evidence_tls, "state", None)
        self._evidence_tls.state = (EvidenceContext(self.catalog), {})
        try:
            yield
        finally:
            self._evidence_tls.state = outer

    def _intervals(self, node: PlanNode) -> list:
        """The proven interval of each output channel of ``node``
        (``analysis.kernel_soundness.channel_values``) under this
        thread's ``_proving`` scope."""
        from presto_tpu.analysis.kernel_soundness import channel_values

        return channel_values(node, *self._evidence_tls.state)

    def _evidence_leaf(self, leaf: PlanNode) -> PlanNode:
        """``leaf`` for ``_source_pages``: inside a ``_proving`` scope
        a scan is held to the splits counted when its domains were
        read (``EvidenceContext.splits``; read now if no chain has
        asked yet, so that one that asks later cannot be proved by a
        younger table than was scanned): a row appended since is the
        next statement's.  Any other leaf as it is.  Asked on the
        query's thread: the scheduler's producer has no scope."""
        state = getattr(self._evidence_tls, "state", None)
        if state is None or not isinstance(leaf, TableScanNode):
            return leaf
        state[0].channels(leaf)
        n = state[0].splits.get(id(leaf))
        if n is None:
            return leaf
        return dataclasses.replace(leaf, splits=list(range(n)))

    def arith_report(self, plan: PlanNode) -> List[str]:
        """EXPLAIN (TYPE VALIDATE)'s ``arithmetic:`` block: what this
        runner's chains would prove of ``plan`` now."""
        from presto_tpu.analysis.kernel_soundness import (
            EvidenceContext, arith_report,
        )

        return arith_report(plan, EvidenceContext(self.catalog),
                            self._max_groups)

    def _exclusive_times(self, plan: PlanNode) -> Dict[PlanNode, float]:
        out: Dict[PlanNode, float] = {}

        def walk(n: PlanNode) -> None:
            chain = self._lower(n)
            if chain.stages:
                try:
                    self._time_chain(chain, out)
                except Exception as e:
                    # attribution is best-effort diagnostics, but a
                    # failure must not be invisible (VERDICT r3): the
                    # operator reading VERBOSE output needs to know the
                    # numbers are missing rather than zero
                    import logging

                    logging.getLogger("presto_tpu.explain").warning(
                        "EXPLAIN ANALYZE VERBOSE attribution failed for "
                        "%s chain: %s: %s", type(n).__name__,
                        type(e).__name__, e)
                    out.setdefault(n, float("nan"))
            for j in chain.joins:
                walk(j.sources[1])  # a build side is its own tree
            for s in chain.leaf.sources:
                walk(s)

        with self._proving():  # the guards the query's programs had
            walk(plan)
        return out

    def _time_chain(self, chain: Chain, out: Dict[PlanNode, float]) -> None:
        """Time prefix programs of ``chain`` and record per-member
        deltas (and the leaf's own source time)."""
        import time

        leaf = chain.leaf
        t0 = time.perf_counter()
        pages = list(self._source_pages(leaf))
        jax.block_until_ready(pages)
        if isinstance(leaf, (TableScanNode, ValuesNode, PrecomputedNode)):
            # breaker leaves (agg/sort/expanding join) keep inclusive
            # wall from QueryStats; an "excl" there would double-count
            out[leaf] = time.perf_counter() - t0
        if not pages:
            return

        # a prefix that holds the chain's compacting probe compacts as
        # the chain does, so a member's time is its time in the program
        # that runs (the compaction's is booked to the probe)
        consts = {f"build_{i}": self._materialize_build(j)
                  for i, j in enumerate(chain.joins)}
        prev = 0.0
        for upto, stage in enumerate(chain.stages, 1):
            if stage.node is None:
                continue
            fn = jax.jit(chain.fn(upto)) if self.jit else chain.fn(upto)
            jax.block_until_ready([fn(p, consts) for p in pages])  # compile
            t0 = time.perf_counter()
            jax.block_until_ready([fn(p, consts) for p in pages])
            t = time.perf_counter() - t0
            out[stage.node] = max(t - prev, 0.0)
            prev = t

    # ------------------------------------------------------------------
    def _execute_to_page(self, node: PlanNode) -> Page:
        pages = list(self._pages(node))
        if not pages:
            return Page.empty(node.output_types, 1)
        return concat_pages_device(pages)

    def _pages(self, node: PlanNode) -> Iterator[Page]:
        """Stream output pages of ``node`` (pull model, Driver analog),
        recording per-stage stats when enabled (OperatorContext /
        OperatorStats analog, operator/OperatorStats.java:38 — times
        here are inclusive of the stage's inputs since chains fuse) and
        per-pull operator spans when the query traces.  Tracer-only
        runs skip the row-count device sync — tracing must not change
        the execution profile it measures."""
        from presto_tpu.analysis import range_sanitizer_enabled
        from presto_tpu.obs.trace import current_tracer

        tracer = current_tracer()
        sanitize = range_sanitizer_enabled()
        if self.stats is None and tracer is None and not sanitize:
            yield from self._pages_impl(node)
            return
        import time

        gen = self._pages_impl(node)
        name = type(node).__name__
        label = "op:" + (name[:-4] if name.endswith("Node") else name)
        cat = "exchange" if isinstance(node, RemoteSourceNode) else "operator"
        while True:
            t0 = time.perf_counter()
            try:
                if tracer is not None:
                    with tracer.span(label, cat):
                        p = next(gen)
                else:
                    p = next(gen)
            except StopIteration:
                return
            if self.stats is not None:
                wall = time.perf_counter() - t0
                rows = int(np.asarray(p.num_rows()))
                try:
                    from presto_tpu.memory import page_bytes

                    nb = page_bytes(p)
                except Exception:
                    nb = 0  # byte accounting is best-effort
                self.stats.record(node, wall, rows, nb)
            if sanitize:
                self._sanitize_page(node, p)
            yield p

    def _sanitize_page(self, node: PlanNode, page: Page) -> None:
        """PRESTO_TPU_RANGE_SANITIZER cross-check: every page crossing
        a stage boundary is tested against the abstract interpreter's
        predicted per-channel intervals (analysis/kernel_soundness.
        predicted_intervals).  An observed value outside its predicted
        interval means a transfer function under-approximates — that is
        a checker bug, and it fails LOUDLY here rather than silently
        missing real overflows forever."""
        from presto_tpu.analysis.kernel_soundness import predicted_intervals
        from presto_tpu.obs import METRICS

        memo = getattr(self, "_range_pred_memo", None)
        if memo is None:
            memo = self._range_pred_memo = {}
        if id(node) not in memo:
            # the root call fills the whole subtree in one analysis;
            # nodes the analyzer has no prediction for map to None
            memo.update(predicted_intervals(node))
            memo.setdefault(id(node), None)
        preds = memo[id(node)]
        if not preds:
            return
        for i, pred in enumerate(preds):
            if pred is None or i >= len(page.blocks):
                continue
            b = page.blocks[i]
            if getattr(b.data, "ndim", 0) != 1:
                continue
            live = np.asarray(page.row_mask & b.valid)
            if not live.any():
                continue
            vals = np.asarray(b.data)[live]
            lo, hi = pred
            mn, mx = int(vals.min()), int(vals.max())
            if mn < lo or mx > hi:
                METRICS.counter("kernel.sanitizer_escapes").inc()
                name = (node.output_names[i]
                        if i < len(node.output_names) else f"${i}")
                raise RuntimeError(
                    f"range sanitizer: {type(node).__name__} channel "
                    f"{i} ({name!r}) observed [{mn}, {mx}] outside the "
                    f"predicted interval [{lo}, {hi}] — an abstract "
                    "transfer under-approximates (analysis/ranges.py)")

    def _pages_impl(self, node: PlanNode) -> Iterator[Page]:
        if isinstance(node, OutputNode):
            yield from self._pages(node.source)
            return

        if isinstance(node, LimitNode):
            remaining = node.count
            for p in self._pages(node.source):
                if remaining <= 0:
                    return
                p = limit_page(p, remaining)
                remaining -= int(host_read(p.num_rows(), "limit_rows"))
                yield p
            return

        if isinstance(node, SortNode):
            sort_exprs = list(node.sort_exprs)
            ascending = list(node.ascending)
            nulls_first = node.nulls_first

            def do_sort(p):
                with jax.named_scope("op:Sort"):
                    return sort_page(p, sort_exprs, ascending, nulls_first)

            _named(do_sort, "sort")
            fn = self._program(
                "sort", (sort_exprs, ascending, nulls_first),
                lambda: jax.jit(do_sort) if self.jit else do_sort)
            pages = list(self._pages(node.source))
            if len(pages) > 1 and self.merge_sort:
                # distributed-sort shape: sort each producer page, then
                # an order-preserving k-way merge (MergeOperator.java:45
                # + MergeHashSort) — no monolithic re-sort of the union
                from presto_tpu.ops.merge import merge_sorted_pages

                sorted_pages = [fn(p) for p in pages]
                for p in sorted_pages:
                    self._account("sort_input", p)
                yield merge_sorted_pages(sorted_pages, sort_exprs,
                                         ascending, nulls_first)
                return
            src = concat_pages_device(pages) if pages else Page.empty(
                node.output_types, 1)
            self._account("sort_input", src)
            yield fn(src)
            return

        if isinstance(node, TopNNode):
            yield self._run_topn(node)
            return

        if isinstance(node, AggregationNode) and node.step in ("single", "final"):
            yield self._run_aggregation(node)
            return

        if isinstance(node, ValuesNode):
            cols, valids = [], []
            for i, t in enumerate(node.types):
                raw = [r[i] for r in node.rows]
                valids.append(np.asarray([v is not None for v in raw], np.bool_))
                if t.is_array or t.is_map or t.is_long_decimal:
                    # Page encodes container lists / limb decimals
                    # (unscaled ints may exceed int64 at p > 18)
                    cols.append(raw)
                else:
                    cols.append(np.asarray([0 if v is None else v for v in raw],
                                           dtype=t.np_dtype))
            yield Page.from_arrays(cols, node.types, valids=valids,
                                   dictionaries=node.dictionaries)
            return

        if isinstance(node, PrecomputedNode):
            yield node.page
            return

        if isinstance(node, RemoteSourceNode):
            # worker-to-worker shuffle read: pull this stage's partition
            # from every upstream task's output buffer
            from presto_tpu.server.serde import deserialize_page
            from presto_tpu.server.shuffle_client import pull_pages

            dicts = [c.dictionary for c in node.channels]
            for uri, tid in node.tasks:
                for raw in pull_pages(uri, tid, node.buffer_id):
                    yield deserialize_page(raw, dicts)
            return

        if isinstance(node, UnionNode):
            from presto_tpu.parallel.fragment import remap_union_leg_page

            chans = node.channels
            for k, src in enumerate(node.inputs):
                offs = node.code_offsets[k]
                for p in self._pages(src):
                    yield remap_union_leg_page(p, offs, chans)
            return

        if isinstance(node, WindowNode):
            from presto_tpu.ops.window import window_page

            src = self._execute_to_page(node.source)
            partition_exprs = list(node.partition_exprs)
            order_exprs = list(node.order_exprs)
            ascending = list(node.ascending)
            funcs = list(node.funcs)
            pd = node.partition_domains

            def do_window(p):
                return window_page(
                    p, partition_exprs, order_exprs, ascending, funcs,
                    partition_domains=pd,
                )

            fn = self._program(
                "window",
                (partition_exprs, order_exprs, ascending, funcs, pd),
                lambda: jax.jit(do_window) if self.jit else do_window)
            yield fn(src)
            return

        if isinstance(node, GroupIdNode):
            yield from self._groupid_pages(node)
            return

        if isinstance(node, UnnestNode):
            from presto_tpu.ops.container import unnest_expand

            exprs = list(node.unnest_exprs)
            ordinality = node.ordinality
            chans = node.channels

            def do_unnest(p: Page) -> Page:
                return unnest_expand(p, exprs, ordinality, chans)

            fn = self._program(
                "unnest",
                (exprs, ordinality,
                 [(c.type, c.dictionary) for c in chans]),
                lambda: jax.jit(do_unnest) if self.jit else do_unnest)
            for p in self._pages(node.source):
                yield fn(p)
            return

        if isinstance(node, JoinNode) and node.use_index:
            yield from self._index_join_pages(node)
            return

        if isinstance(node, JoinNode) and not self._streaming(node):
            yield from self._expanding_join_pages(node)
            return

        # streaming chain rooted at a scan or breaker
        yield from self._chain_pages(node)

    def _streaming(self, node: JoinNode) -> bool:
        # index joins must not fuse into chains: the chain builder would
        # materialize the full build scan instead of point lookups
        return (is_streaming_join(node) and node not in self._force_expanding
                and not node.use_index)

    # ------------------------------------------------------------------
    # streaming-chain compilation
    # ------------------------------------------------------------------
    def _chain_pages(self, node: PlanNode) -> Iterator[Page]:
        from presto_tpu.memory import ExceededMemoryLimitError

        # pop the unordered grant FIRST: it applies to this chain only,
        # never to nested chains pulled while materializing builds
        unordered = self._take_unordered()
        # only the chain of the aggregation that asked, and can start
        # again, may compact; not after a miss
        restartable = getattr(self._restartable_tls, "root", None) is node
        chain = self._lower(node, None if restartable else 0)
        joins = chain.joins
        try:
            consts = {f"build_{i}": self._materialize_build(j) for i, j in enumerate(joins)}
        except ExceededMemoryLimitError as e:
            victim = next((j for j in joins if f"join_build@{id(j)}#" in e.tag), None)
            if victim is None:
                raise
            # demote the oversized build's join out of the fused chain;
            # it re-plans through the partitioned (spilled) join path
            self._force_expanding.add(victim)
            yield from self._pages_impl(node)
            return
        from presto_tpu.obs import span

        # the rest of the lowering, once the builds are there: the
        # program by the chain's signature, its counts, the leaf's
        # split count from the query's evidence
        with span("chain:lower", cat="operator"):
            fn = self._chain_program(chain)
            checked, proven = chain.arith_counts()
            _COUNTS.arith_checked = getattr(
                _COUNTS, "arith_checked", 0) + checked
            _COUNTS.arith_proven = getattr(
                _COUNTS, "arith_proven", 0) + proven
            _COUNTS.chain_probes = getattr(
                _COUNTS, "chain_probes", 0) + chain.probes
            leaf = self._evidence_leaf(chain.leaf)
        if not chain.compacts:
            yield from self._chain_outputs(leaf, fn, consts, unordered)
            return
        # A compacting program answers for the rows that fitted its
        # small page and says whether all did.  Each page goes to the
        # consumer as it is produced and only the ``over`` scalars are
        # kept; after the last split ONE read says whether any page
        # held more, and if so this raises: the consumer drops what it
        # built and starts again over the chain that does not compact.
        # So no row is ever dropped, whatever the estimate was worth.
        from presto_tpu.obs import METRICS

        flags = []
        for page, over in self._chain_outputs(leaf, fn, consts, unordered):
            flags.append(over)
            yield page
        fitted = not any(host_read(flags, "compact_taken"))
        which = "compacted" if fitted else "fallback"
        setattr(_COUNTS, which,
                getattr(_COUNTS, which, 0) + len(flags))
        METRICS.counter("chain.compact_pages" if fitted
                        else "chain.compact_fallback_pages").inc(len(flags))
        if not fitted:
            self._no_compact.add(node)
            raise CompactionMissed(node)

    def _chain_program(self, chain: Chain):
        """``chain``'s program, named after its stages and compiled, or
        the registry's program of the same signature."""
        def make():
            stage = _named(chain.fn(), chain.name())
            return jax.jit(stage) if self.jit else stage

        return self._program("chain", chain.signature(), make)

    def _chain_outputs(self, leaf: PlanNode, fn, consts,
                       unordered: bool) -> Iterator:
        """``fn(page, consts)`` of every page of ``leaf``."""
        mem = self._mem
        # the scheduler takes SCAN pipelines (independent connector
        # splits — the morsel shape); breaker-leaf chains keep the
        # serial pull, since their "source" is a materialized upstream
        # whose own execution must stay on this thread (thread-local
        # memory context and build registries)
        if self.task_concurrency <= 1 or not isinstance(leaf, TableScanNode):
            # serial leg (task_concurrency=1): the exact legacy pull
            # loop — no threads, no reordering, the A/B baseline.
            # Split accounting covers SCAN pipelines only — breaker-leaf
            # chains pull materialized pages, not connector splits, and
            # counting them would make the splits surface meaningless
            count_splits = isinstance(leaf, TableScanNode)
            for page in self._source_pages(leaf):
                tag = None
                if mem is not None:
                    from presto_tpu.memory import page_bytes

                    # transient: the in-flight scan page is accountable
                    # while the chain program consumes it, but soft — a
                    # streaming input can't be spilled; it is bounded by
                    # split capacity, not by the pool
                    tag = mem.reserve("scan_page", page_bytes(page),
                                      enforce=False)
                if count_splits:
                    self._task_stats.splits += 1
                try:
                    yield fn(page, consts)
                finally:
                    # early generator exit (LIMIT) must not leak the tag
                    if tag is not None:
                        mem.free(tag)
            return
        yield from self._chain_pages_scheduled(leaf, fn, consts, mem,
                                               unordered)

    def _chain_pages_scheduled(self, leaf: PlanNode, fn, consts, mem,
                               unordered: bool) -> Iterator[Page]:
        """Morsel-driven chain execution: up to ``task_concurrency``
        splits in flight on the scheduler's worker pool, host page prep
        prefetched ahead, results delivered in source order (or
        completion order when the consumer granted it).  Backpressure:
        dispatch defers while the memory pool has no headroom, so
        concurrency throttles instead of OOMing."""
        from presto_tpu.exec.tasks import SplitScheduler

        def produced():
            for page in self._source_pages(leaf):
                tag = None
                if mem is not None:
                    from presto_tpu.memory import page_bytes

                    # soft reservation, exactly like the serial leg —
                    # tagged per split so in-flight pages are visible
                    # in the pool books while they await execution
                    tag = mem.reserve("scan_page", page_bytes(page),
                                      enforce=False)
                yield page, tag

        def run_split(item):
            page, tag = item
            try:
                return fn(page, consts)
            finally:
                if tag is not None:
                    mem.free(tag)

        def drop_split(item):
            # produced-but-never-executed split on early close (LIMIT):
            # its reservation must not linger until query end, where it
            # would skew headroom backpressure and spill decisions
            _, tag = item
            if tag is not None:
                mem.free(tag)

        headroom = None
        if mem is not None:
            headroom = lambda: mem.headroom() > 0  # noqa: E731
        sched = SplitScheduler(
            concurrency=self.task_concurrency, prefetch=self.task_prefetch,
            ordered=not unordered, headroom=headroom, name="chain",
            stats=self._task_stats,
            drop=drop_split if mem is not None else None)
        yield from sched.map(produced(), run_split)

    def _source_pages(self, node: PlanNode) -> Iterator[Page]:
        if isinstance(node, TableScanNode):
            conn = self.catalog.connector(node.handle.connector_name)
            idx = list(node.columns)
            # split enumeration happens at EXECUTION time, not plan time
            # (DistributedExecutionPlanner opens SplitSources during
            # planDistribution, so cached plans see connector-side
            # changes — e.g. shardstore compaction/rebalance); a scan
            # whose domains a chain was proved by comes with the splits
            # counted then (``_evidence_leaf``)
            if node.splits is not None:
                splits = node.splits
            else:
                splits = range(conn.num_splits(node.handle.table)
                               if hasattr(conn, "num_splits")
                               else node.handle.num_splits)
            td = None
            if node.constraints and hasattr(conn, "split_stats"):
                from presto_tpu.predicate import TupleDomain

                td = TupleDomain.from_constraints(node.constraints)
                if td.is_none:
                    return  # provably empty scan
            sample = node.sample
            produced = 0
            # live progress: one stage per scan invocation (self-join
            # twins and capacity retries each get their own entry; the
            # reported percentage is a running max, so re-runs never
            # regress it).  Rows are padded row SLOTS — counting live
            # rows would force a device sync per split.
            from presto_tpu.obs import current_progress, current_timeline

            prog = current_progress()
            tl = current_timeline()
            stage_name = None
            if prog is not None:
                stage_name = prog.new_stage_name(
                    f"scan:{node.handle.table}")
                try:
                    total = len(splits)
                except TypeError:
                    total = None
                prog.stage(stage_name, splits_total=total)

            def _split_mark(page=None):
                if tl is not None and stage_name is not None:
                    # one point per finished split, named by stage — the
                    # timeline's scan-progress track (value is always 1;
                    # consumers count points, not sum values)
                    tl.record(f"splits_done.{stage_name}", 1.0)
                if prog is None:
                    return
                if page is None:
                    prog.split_done(stage_name)
                    return
                from presto_tpu.memory import page_bytes

                prog.split_done(stage_name, rows=page.capacity,
                                nbytes=page_bytes(page))
            # scan-uniform capacity: a split that FITS a previously
            # established bucket of this scan (and is at least a third
            # of it) joins that bucket instead of opening its own, so the
            # whole scan runs ONE chain program — this catches both the
            # ragged tail and the boundary-straddle siblings without
            # consulting bucket_capacity's slack again (an exact-size
            # generator's just-short tail must NOT overshoot past the
            # full splits' bucket).  Much smaller splits keep their own
            # bucket: padding a sliver to full capacity would multiply
            # its compute, not add +6%.
            cap_hi = 0
            for split in splits:
                if node.limit is not None and produced >= node.limit:
                    break  # pushed-down LIMIT satisfied: skip the rest
                if sample is not None and sample[0] == "system":
                    # SYSTEM(p): keep whole splits by a deterministic
                    # split hash (SampleNode SYSTEM semantics); mixed so
                    # split 0 is not a fixed point
                    h = (((split + 1) * 2654435761) ^ 0x9E3779B9) % 10_000
                    if h >= sample[1] * 100:
                        _split_mark()
                        continue
                if td is not None:
                    stats = conn.split_stats(node.handle.table, split)
                    if not td.overlaps_split_stats(stats):
                        _split_mark()  # pruned splits still count as done
                        continue
                page = conn.page_for_split(
                    node.handle.table, split, capacity=self.split_capacity
                )
                if sample is not None and sample[0] == "bernoulli":
                    # BERNOULLI(p): deterministic per-(split, row) hash
                    # mask — every row kept with probability p%
                    r = jnp.arange(page.capacity, dtype=jnp.uint32)
                    h = (r + jnp.uint32(split) * jnp.uint32(0x9E3779B1))
                    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA6B)
                    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
                    keep = (h % jnp.uint32(10_000)) < jnp.uint32(
                        int(sample[1] * 100))
                    page = Page(page.blocks, page.row_mask & keep)
                if node.limit is not None:
                    produced += int(
                        host_read(page.row_mask, "limit_rows").sum())
                raw = Page(tuple(page.blocks[i] for i in idx), page.row_mask)
                if 0 < raw.capacity <= cap_hi \
                        and raw.capacity * 3 >= cap_hi:
                    out = pad_page_to(raw, cap_hi)
                else:
                    out = pad_page_pow2(raw)
                    if out.capacity > cap_hi:
                        cap_hi = out.capacity
                _split_mark(out)
                yield out
            if prog is not None:
                prog.finish_stage(stage_name)
        else:
            yield from self._pages(node)

    def _materialize_build(self, node):
        if node not in self._builds:
            if isinstance(node, CrossSingleNode):
                build_page = self._execute_to_page(node.right)
                self._builds[node] = slice_page(
                    host_read(build_page, "cross_single").compact_host(), 1)
            else:
                pages = tuple(self._pages(node.right))
                if not pages:
                    pages = (Page.empty(node.right.output_types, 1),)

                def build_fn(uniq: bool):
                    right_keys = list(node.right_keys)
                    kd = node.key_domains
                    ns = getattr(node, "null_safe_keys", False)

                    def make_build(ps):
                        # bucket the build capacity (concat sums the
                        # producers' caps — a data-dependent shape
                        # every downstream probe program would bake
                        # in; padding dead rows restores the ladder)
                        with jax.named_scope("op:JoinBuild"):
                            return build_join(
                                pad_page_pow2(
                                    concat_pages_device(list(ps))),
                                right_keys,
                                key_domains=kd, null_safe=ns, unique=uniq,
                            )

                    _named(make_build, "join_build")
                    return self._program(
                        "join_build",
                        (right_keys, tuple(kd or ()), ns, uniq),
                        lambda: jax.jit(make_build) if self.jit
                        else make_build)

                uniq = bool(getattr(node, "unique_build", False))
                build = build_fn(uniq)(pages)
                if build.unique_ok is not None and not bool(
                        host_read(build.unique_ok, "unique_ok")):
                    # the planner's uniqueness promise failed at runtime
                    # (PagesHash would have chained): rebuild sorted
                    build = build_fn(False)(pages)
                self._account("join_build", build.page, node)
                self._builds[node] = build
        return self._builds[node]

    # ------------------------------------------------------------------
    def _expanding_join_pages(self, node: JoinNode) -> Iterator[Page]:
        """Many-to-many probe with capacity retry (the analog of the
        reference's yielding LookupJoinPageBuilder). A build side that
        exceeds the pool falls back to host-RAM partitioned join."""
        from presto_tpu.memory import ExceededMemoryLimitError

        if node in self._force_expanding:
            yield from self._partitioned_join_pages(node)
            return
        try:
            build = self._materialize_build(node)
        except ExceededMemoryLimitError as e:
            if f"join_build@{id(node)}#" not in e.tag:
                raise
            yield from self._partitioned_join_pages(node)
            return
        kd = node.key_domains
        left_keys = list(node.left_keys)
        build_output = list(range(len(node.right.channels)))
        is_full = node.kind == "full"
        kind = "left" if is_full else node.kind
        ns = node.null_safe_keys

        def probe(b, p, out_capacity):
            with jax.named_scope("op:Join"):
                return probe_expand(
                    b, p, left_keys, out_capacity, key_domains=kd,
                    kind=kind, build_output=build_output,
                    return_matched=is_full, null_safe=ns,
                )

        _named(probe, "join_probe")

        fn = self._program(
            "join_probe",
            (left_keys, tuple(kd or ()), kind, tuple(build_output),
             is_full, ns),
            lambda: jax.jit(probe, static_argnames=("out_capacity",))
            if self.jit else probe)

        matched_acc = None
        for p in self._pages(node.left):
            res = _probe_with_retry(
                lambda b, pg, cap: fn(b, pg, out_capacity=cap), build, p)
            yield res[0]
            if is_full:
                matched_acc = res[2] if matched_acc is None else matched_acc | res[2]

        if is_full:
            from presto_tpu.ops.join import outer_build_tail

            if matched_acc is None:
                matched_acc = jnp.zeros((build.page.capacity,), dtype=jnp.bool_)
            probe_spec = [(c.type, c.dictionary) for c in node.left.channels]
            yield outer_build_tail(build, matched_acc, probe_spec, build_output)

    # ------------------------------------------------------------------
    def _groupid_pages(self, node: GroupIdNode) -> Iterator[Page]:
        """Emit each source page once per grouping set: source blocks +
        key blocks (inactive keys NULL-masked) + constant $group_id
        (GroupIdOperator.java analog; replication stays on device)."""
        from presto_tpu.expr.compile import ExprCompiler

        key_exprs = list(node.key_exprs)
        nsrc = len(node.source.channels)
        key_chans = node.channels[nsrc:nsrc + len(key_exprs)]
        gid_type = node.channels[-1].type

        def make(mask, gid):
            def run(p: Page) -> Page:
                comp = ExprCompiler.for_page(p)
                blocks = list(p.blocks)
                for e, live, ch in zip(key_exprs, mask, key_chans):
                    d, v = comp.compile(e)(p)
                    if not live:
                        v = jnp.zeros_like(v)
                    blocks.append(Block(d, v, e.type, ch.dictionary))
                gid_data = jnp.full((p.capacity,), gid, dtype=jnp.int64)
                blocks.append(
                    Block(gid_data, jnp.ones(p.capacity, dtype=jnp.bool_),
                          gid_type)
                )
                return Page(tuple(blocks), p.row_mask)

            return run

        fns = [
            self._program(
                "groupid",
                (tuple(key_exprs),
                 [(c.type, c.dictionary) for c in key_chans],
                 tuple(bool(b) for b in mask), gid, gid_type),
                lambda m=mask, g=gid: jax.jit(make(m, g)) if self.jit
                else make(m, g))
            for gid, mask in enumerate(node.set_masks)
        ]
        for p in self._pages(node.source):
            for fn in fns:
                yield fn(p)

    # ------------------------------------------------------------------
    def _index_join_pages(self, node: JoinNode) -> Iterator[Page]:
        """Index join: fetch build rows per probe batch through the
        connector's point-lookup SPI (operator/index/IndexLoader.java +
        IndexSourceOperator.java).  Each probe page's distinct key
        tuples go to the connector; only matching build rows ever
        materialize."""
        from presto_tpu.expr.compile import ExprCompiler

        scan: TableScanNode = node.right
        conn = self.catalog.connector(scan.handle.connector_name)
        key_cols = [
            scan.handle.columns[scan.columns[k.index]].name
            for k in node.right_keys
        ]
        left_keys = list(node.left_keys)
        right_keys = list(node.right_keys)
        build_output = list(range(len(node.right.channels)))
        col_idx = list(scan.columns)

        for p in self._pages(node.left):
            ph = p.compact_host()
            c = ExprCompiler.for_page(ph)
            lanes = []
            sel = np.asarray(ph.row_mask)
            for e in left_keys:
                d, v = c.compile(e)(ph)
                lanes.append(np.asarray(d))
                sel = sel & np.asarray(v)
            keys = {tuple(int(lane[i]) for lane in lanes)
                    for i in np.nonzero(sel)[0]}
            fetched = conn.index_lookup(scan.handle.table, key_cols, sorted(keys))
            pruned = [Page(tuple(fp.blocks[i] for i in col_idx), fp.row_mask)
                      for fp in fetched]
            bpage = concat_pages_device(pruned) if pruned else Page.empty(
                node.right.output_types, 1)
            build = build_join(bpage, right_keys, key_domains=None)
            self._account("index_join_build", build.page, node)
            if node.kind in ("semi", "anti", "mark"):
                yield probe_join(build, p, left_keys, key_domains=None,
                                 kind=node.kind, build_output=build_output,
                                 null_aware=getattr(node, "null_aware", False))
            elif node.unique_build:
                yield probe_join(build, p, left_keys, key_domains=None,
                                 kind=node.kind, build_output=build_output)
            else:
                def probe_fn(b, pp, out_capacity):
                    return probe_expand(
                        b, pp, left_keys, out_capacity, key_domains=None,
                        kind=node.kind, build_output=build_output,
                    )

                res = _probe_with_retry(probe_fn, build, p)
                yield res[0]

    # ------------------------------------------------------------------
    def _partitioned_join_pages(self, node: JoinNode) -> Iterator[Page]:
        """Spilled hash join: both sides hash-partition by join key into
        host-RAM buckets, then each partition joins independently on
        device — build state is bounded by the largest partition
        (reference: spilled lookup joins,
        operator/SpilledLookupSourceHandle.java +
        GenericPartitioningSpiller)."""
        from presto_tpu.exec.spill import HostPage, make_bucket_fn, partition_to_host
        from presto_tpu.ops.join import outer_build_tail

        K = self.spill_partitions
        kd = node.key_domains
        left_keys = list(node.left_keys)
        right_keys = list(node.right_keys)
        build_output = list(range(len(node.right.channels)))
        is_full = node.kind == "full"
        kind = "left" if is_full else node.kind
        ns = node.null_safe_keys
        right_types = node.right.output_types

        bfn_r = self._program(
            "spill_bucket", (tuple(right_keys), tuple(kd or ()), K),
            lambda: make_bucket_fn(right_keys, kd, K, jit=self.jit))
        bfn_l = self._program(
            "spill_bucket", (tuple(left_keys), tuple(kd or ()), K),
            lambda: make_bucket_fn(left_keys, kd, K, jit=self.jit))

        bbuckets: List[List[HostPage]] = [[] for _ in range(K)]
        for p in self._pages(node.right):
            for k, hp in enumerate(partition_to_host(p, bfn_r(p), K)):
                if hp is not None:
                    bbuckets[k].append(hp)
        pbuckets: List[List[HostPage]] = [[] for _ in range(K)]
        for p in self._pages(node.left):
            for k, hp in enumerate(partition_to_host(p, bfn_l(p), K)):
                if hp is not None:
                    pbuckets[k].append(hp)

        # three-valued IN/NOT IN needs GLOBAL build flags: a NULL build
        # key in one partition makes unmatched probes in EVERY partition
        # UNKNOWN, and "build nonempty" is a whole-relation property
        na = getattr(node, "null_aware", False) and kind in ("semi", "anti",
                                                             "mark")
        g_has_null = g_nonempty = None
        if na:
            from presto_tpu.expr.ir import ColumnRef as _CR

            g_has_null = jnp.asarray(False)
            g_nonempty = jnp.asarray(False)
            plain = all(isinstance(k_, _CR) for k_ in right_keys)
            for k in range(K):
                for hp in bbuckets[k]:
                    if plain:
                        # host-side flags from the spilled numpy columns
                        # — no device rehydrate just for two booleans
                        av = np.ones(len(hp.mask), dtype=bool)
                        for k_ in right_keys:
                            av &= np.asarray(hp.columns[k_.index][1])
                        g_has_null = g_has_null | bool(
                            (hp.mask & ~av).any())
                        g_nonempty = g_nonempty | bool(hp.mask.any())
                    else:
                        from presto_tpu.ops.join import build_null_flags

                        h, ne = build_null_flags(hp.rehydrate(), right_keys)
                        g_has_null = g_has_null | h
                        g_nonempty = g_nonempty | ne

        probe_spec = [(c.type, c.dictionary) for c in node.left.channels]
        for k in range(K):
            if not pbuckets[k] and not (is_full and bbuckets[k]):
                continue
            if bbuckets[k]:
                bpage = concat_pages_device([hp.rehydrate() for hp in bbuckets[k]])
            else:
                bpage = Page.empty(right_types, 1)
            build = build_join(bpage, right_keys, key_domains=kd, null_safe=ns)
            if na:
                build = dataclasses.replace(
                    build, has_null_key=g_has_null, nonempty=g_nonempty)
            tag = None
            if self._mem is not None:
                from presto_tpu.memory import page_bytes

                tag = self._mem.reserve(f"join_build_partition@{id(node)}",
                                        page_bytes(build.page))
            def probe_fn(b, p, out_capacity):
                return probe_expand(
                    b, p, left_keys, out_capacity, key_domains=kd,
                    kind=kind, build_output=build_output, return_matched=is_full,
                    null_safe=ns,
                )

            matched_acc = None
            for hp in pbuckets[k]:
                p = hp.rehydrate()
                if kind in ("semi", "anti", "mark"):
                    yield probe_join(build, p, left_keys, key_domains=kd,
                                     kind=kind, build_output=build_output,
                                     null_safe=ns, null_aware=na)
                    continue
                res = _probe_with_retry(probe_fn, build, p)
                yield res[0]
                if is_full:
                    matched_acc = res[2] if matched_acc is None else matched_acc | res[2]
            if is_full:
                if matched_acc is None:
                    matched_acc = jnp.zeros((build.page.capacity,), dtype=jnp.bool_)
                yield outer_build_tail(build, matched_acc, probe_spec, build_output)
            if tag is not None:
                self._mem.free(tag)  # partition done; its build leaves HBM

    # ------------------------------------------------------------------
    def _run_topn(self, node: TopNNode) -> Page:
        """Fold: keep a device-resident accumulator of exactly ``count``
        rows; each input page is sorted together with the accumulator
        and truncated (TopNOperator.java bounded-heap analog)."""
        n = node.count
        sort_exprs = list(node.sort_exprs)
        ascending = list(node.ascending)
        nulls_first = node.nulls_first

        def fold(acc: Optional[Page], p: Page) -> Page:
            with jax.named_scope("op:TopN"):
                cand = p if acc is None else concat_pages_device([acc, p])
                s = sort_page(cand, sort_exprs, ascending, nulls_first)
                keep = jnp.arange(s.capacity) < n
                return slice_page(Page(s.blocks, s.row_mask & keep), n)

        _named(fold, "topn")

        fold_fn = self._program(
            "topn", (n, sort_exprs, ascending, nulls_first),
            lambda: jax.jit(fold) if self.jit else fold)

        acc: Optional[Page] = None
        for p in self._pages(node.source):
            acc = fold_fn(acc, p)
        if acc is None:
            return Page.empty(node.output_types, max(n, 1))
        return acc

    # ------------------------------------------------------------------
    def _max_groups(self, node: AggregationNode) -> int:
        if node in self._agg_overrides:
            return self._agg_overrides[node]
        kd = node.key_domains
        if node.group_exprs and kd and all(d is not None for d in kd):
            prod = 1
            for lo, hi in kd:
                prod *= hi - lo + 2
            if prod <= node.max_groups:
                return prod
        return node.max_groups

    def _exact_capacity(self, node: AggregationNode, mg: int) -> bool:
        kd = node.key_domains
        if node.group_exprs and kd and all(d is not None for d in kd):
            prod = 1
            for lo, hi in kd:
                prod *= hi - lo + 2
            return prod <= mg
        return False

    def _packed_direct(self, node: AggregationNode, mg: int) -> bool:
        """True when the chain's partial aggregation takes the
        packed-direct layout (group id == slot position): exact domains
        AND within DIRECT_GROUP_LIMIT — mirrors grouped_aggregate's own
        branch condition.  Above the limit the sort path emits
        front-compacted pages instead, where position says nothing."""
        from presto_tpu.ops.aggregate import packed_direct_layout

        # presorted partials take grouped_aggregate's STREAMING branch
        # (front-compacted, first-appearance order) before packed-direct
        # is even considered — position says nothing there
        if getattr(node, "presorted", False):
            return False
        return packed_direct_layout(node.group_exprs, node.key_domains, mg)

    def _commutative_exact(self, node: AggregationNode) -> bool:
        """True when the aggregation's fold is order-insensitive in
        EXACT arithmetic: count/min/max always, sum only over integer
        representations (integer-like and short decimals — scaled
        int64s).  Float sums/avg stay ordered: float addition is
        non-associative, and concurrency must not change results."""
        for a in node.aggs:
            if a.distinct:
                return False
            if a.fn in ("count", "count_star", "min", "max"):
                continue
            if a.fn == "sum" and (a.type.is_integerlike or a.type.is_decimal):
                # all decimal sums are exact integer folds now — short
                # ones in scaled int64, widened/long ones in base-1e9
                # sum limbs (both associative and commutative)
                continue
            return False
        return True

    def _run_aggregation(self, node: AggregationNode) -> Page:
        """Breaker with spill fallback: the in-place path folds partial
        pages on device; past the pool limit or the capacity threshold
        it re-executes partitioned through host RAM (spiller analog)."""
        from presto_tpu.memory import ExceededMemoryLimitError

        try:
            try:
                out = self._run_aggregation_impl(node)
            except CompactionMissed:
                # a page of the source's chain did not fit its small
                # page: nothing built from that try is kept, and the
                # chain, marked by now, runs whole
                out = self._run_aggregation_impl(node)
            return self._host_finalize_aggs(node, out)
        except ExceededMemoryLimitError as e:
            if f"agg_accumulator@{id(node)}#" not in e.tag:
                raise
        except GroupCapacityExceeded as e:
            if e.node is not node or e.needed <= SPILL_GROUP_THRESHOLD:
                raise
        return self._host_finalize_aggs(
            node, self._run_aggregation_spilled(node))

    def _host_finalize_aggs(self, node: AggregationNode, out: Page) -> Page:
        """Aggregates whose OUTPUT is a string cannot finalize inside
        jit; their jitted finalize emits the numeric state and this
        host pass formats it (evaluate_classifier_predictions — the
        presto-ml output function's role)."""
        if not any(a.fn == "evaluate_classifier_predictions"
                   for a in node.aggs):
            return out
        from presto_tpu.ops.aggregate import ML_MAX_CLASSES
        from presto_tpu.page import Dictionary
        from presto_tpu.types import VARCHAR

        C = ML_MAX_CLASSES
        nkeys = len(node.group_exprs)
        blocks = list(out.blocks)
        for i, agg in enumerate(node.aggs):
            if agg.fn != "evaluate_classifier_predictions":
                continue
            b = blocks[nkeys + i]
            data = np.asarray(b.data)
            valid = np.asarray(b.valid) & np.asarray(out.row_mask)
            live_rows = np.nonzero(valid)[0]
            texts = [""] * data.shape[0]
            for r in live_rows:  # dead padded slots skip formatting
                tp = data[r, 1:1 + C]
                fp = data[r, 1 + C:1 + 2 * C]
                fn = data[r, 1 + 2 * C:1 + 3 * C]
                correct = int(tp.sum())
                total = correct + int(fp.sum())
                pct = 100.0 * correct / total if total else 0.0
                parts = [f"Accuracy: {correct}/{total} ({pct:.2f}%)\n"]
                for cls in range(C):
                    t_, f_, n_ = int(tp[cls]), int(fp[cls]), int(fn[cls])
                    if t_ == 0 and f_ == 0 and n_ == 0:
                        continue
                    pp = 100.0 * t_ / (t_ + f_) if t_ + f_ else 0.0
                    rr = 100.0 * t_ / (t_ + n_) if t_ + n_ else 0.0
                    parts.append(f"Class '{cls}'\n")
                    parts.append(
                        f"Precision: {t_}/{t_ + f_} ({pp:.2f}%)\n")
                    parts.append(f"Recall: {t_}/{t_ + n_} ({rr:.2f}%)\n")
                texts[r] = "".join(parts)
            uniq = sorted({texts[r] for r in live_rows})
            dic = Dictionary(uniq)
            codes = np.zeros(data.shape[0], dtype=np.int32)
            for r in live_rows:
                codes[r] = dic.code_of(texts[r])  # memoized O(1) lookup
            blocks[nkeys + i] = Block(jnp.asarray(codes),
                                      jnp.asarray(valid), VARCHAR, dic)
        return Page(tuple(blocks), out.row_mask)

    def _run_aggregation_spilled(self, node: AggregationNode) -> Page:
        """Lifespan-style partitioned aggregation: hash-partition the
        pre-aggregation rows into host-RAM buckets, then aggregate each
        bucket to completion on device (grouped execution + partitioning
        spiller, execution/Lifespan.java:26 +
        spiller/GenericPartitioningSpiller.java)."""
        from presto_tpu.exec.spill import HostPage, make_bucket_fn, partition_to_host
        from presto_tpu.ops.aggregate import grouped_aggregate

        K = self.spill_partitions
        group_exprs = list(node.group_exprs)
        aggs = list(node.aggs)
        kd = node.key_domains
        num_keys = len(group_exprs)
        partial_input = node.step == "final"
        if partial_input:
            # source emits partial-state pages: keys are the first
            # num_keys channels
            from presto_tpu.expr.ir import ColumnRef

            src_ch = node.source.channels
            bucket_exprs = [ColumnRef(type=src_ch[i].type, index=i)
                            for i in range(num_keys)]
        else:
            bucket_exprs = group_exprs
        bucket_fn = self._program(
            "spill_bucket", (tuple(bucket_exprs), tuple(kd or ()), K),
            lambda: make_bucket_fn(bucket_exprs, kd, K, jit=self.jit))

        buckets: List[List[HostPage]] = [[] for _ in range(K)]
        for p in self._pages(node.source):
            for k, hp in enumerate(partition_to_host(p, bucket_fn(p), K)):
                if hp is not None:
                    buckets[k].append(hp)

        # per-bucket capacity ~ total/K (keys hash-spread); per-bucket
        # doubling below recovers skewed buckets
        cap0 = max(1 << 10, min(self._max_groups(node), SPILL_GROUP_THRESHOLD) // K)

        tower_fns = _AggFoldTower.programs(self, num_keys, aggs, kd)

        def fold_bucket(pages: List[HostPage], cap: int) -> "_AggFoldTower":
            # tower fold with live-extent compaction (same machinery as
            # the in-memory path; account=False — spill state must not
            # re-trip the pool it is relieving)
            tower = _AggFoldTower(self, node, tower_fns, cap, account=False)
            for hp in pages:
                p = hp.rehydrate()
                if partial_input:
                    pp = p
                else:
                    pp = grouped_aggregate(p, group_exprs, aggs, cap,
                                           key_domains=kd, mode="partial")
                tower.add(pp)
            return tower

        outs: List[Page] = []
        for k in range(K):
            if not buckets[k]:
                continue
            cap = cap0
            while True:
                tower = fold_bucket(buckets[k], cap)
                # tower merges are unclamped; only the per-page
                # grouped_aggregate at static ``cap`` can truncate, and
                # a full page is the tell (partial_input pages are
                # already states — nothing truncates)
                if (partial_input or not tower.suspect_truncation
                        or cap >= MAX_AGG_GROUPS):
                    out = tower.finish_single()
                    break
                cap = min(MAX_AGG_GROUPS,
                          max(cap * 2,
                              1 << max(1,
                                       2 * tower.live_total - 1).bit_length()))
            if out is None:  # every page in the bucket was all-dead
                continue
            # bucket outputs are result stream, not operator state — not
            # charged against the pool (the whole point of the spill)
            outs.append(out)
        if not outs:
            out = Page.empty(node.output_types, max(cap0, 1))
            return self._groupid_empty_fixup(node, out)
        if not node.group_exprs:
            # global agg never spills (one group); defensive
            return outs[0]
        return concat_pages_device(outs)

    def _run_aggregation_impl(self, node: AggregationNode) -> Page:
        """Breaker: stream partial pages and fold-merge with a bounded
        accumulator (2*max_groups concat each step, static shapes)."""
        mg = self._max_groups(node)
        if node.step == "final":
            source: PlanNode = node.source
        else:
            # step == 'single': inject a per-page partial step
            partial = self._partial_nodes.get(node)
            if partial is None:
                partial = AggregationNode(
                    source=node.source,
                    group_exprs=node.group_exprs,
                    group_names=node.group_names,
                    aggs=node.aggs,
                    agg_names=node.agg_names,
                    step="partial",
                    max_groups=node.max_groups,
                    presorted=node.presorted,
                )
                self._partial_nodes[node] = partial
            self._agg_overrides[partial] = mg
            source = partial

        # what follows consumes ``source``'s pages here and nowhere
        # else, and ``_run_aggregation`` can run it again: the one
        # consumer a chain may compact for (``_chain_pages``)
        asked = getattr(self._restartable_tls, "root", None)
        self._restartable_tls.root = source
        try:
            return self._fold_partials(node, source, mg)
        finally:
            self._restartable_tls.root = asked

    def _fold_partials(self, node: AggregationNode, source: PlanNode,
                       mg: int) -> Page:
        """``_run_aggregation_impl`` over the partial pages of
        ``source``."""
        aggs = list(node.aggs)
        num_keys = len(node.group_exprs)
        kd = node.key_domains

        if node.group_exprs and not self._packed_direct(node, mg):
            # sort-path partials: live-extent compaction + tower merge.
            # Tower capacities are unclamped, so the merge itself never
            # truncates; the one remaining hazard is the chain's
            # static-capacity per-split partial (only when THIS runner
            # injected it, i.e. step single) — a full partial page
            # triggers ONE retry with the capacity jumped to the
            # observed live total instead of a doubling ladder.
            tower = _AggFoldTower(
                self, node, _AggFoldTower.programs(self, num_keys, aggs, kd),
                mg)
            # exact commutative folds (count/min/max, integer sums) may
            # take chain pages in COMPLETION order: the tower's merged
            # values are order-independent in exact arithmetic, so the
            # scheduler skips the reorder buffer (grant is consume-once
            # and cleared below even if no chain ever claimed it)
            if self.task_concurrency > 1 and self._commutative_exact(node):
                self._unordered_tls.ok = True
            try:
                for p in self._pages(source):
                    tower.add(p)
            except CompactionMissed:
                tower.release()
                raise
            finally:
                self._unordered_tls.ok = False
            if node.step == "single" and tower.suspect_truncation \
                    and not self._exact_capacity(node, mg) \
                    and mg < MAX_AGG_GROUPS:
                needed = min(
                    MAX_AGG_GROUPS,
                    max(mg * 2,
                        1 << max(1, 2 * tower.live_total - 1).bit_length()))
                self._agg_overrides[node] = needed
                raise GroupCapacityExceeded(needed, node)
            out = tower.finish_single()
            if out is None:
                return self._groupid_empty_fixup(
                    node, Page.empty(node.output_types, max(mg, 1)))
            return self._groupid_empty_fixup(node, out)

        # exact-capacity (packed-direct) partials: slot position IS the
        # group key, so the fold is pure ELEMENTWISE state combination —
        # no sort, no scatter, no concat (the direct-address layout's
        # payoff; the classic sort-merge fold re-sorted 2*capacity keys
        # per split)
        from presto_tpu.ops.aggregate import (
            combine_packed_states, finalize_packed, packed_fold_supported,
        )

        # positional fold requires the pages to BE packed-direct, which
        # only this runner's own injected partial guarantees — step
        # 'final' inputs arrive through exchange serde, which compacts
        # live rows and destroys the slot layout
        if node.group_exprs and node.step == "single" \
                and self._packed_direct(node, mg) \
                and packed_fold_supported(aggs):
            def fold_pk(acc: Optional[Page], p: Page) -> Page:
                if acc is None:
                    return p
                with jax.named_scope("op:Aggregation"):
                    return combine_packed_states(acc, p, num_keys, aggs)

            def final_pk(acc: Page) -> Page:
                with jax.named_scope("op:Aggregation"):
                    return finalize_packed(acc, num_keys, aggs)

            _named(fold_pk, "agg_packed_fold")
            _named(final_pk, "agg_packed_final")

            sig = (num_keys, tuple(aggs))
            fold_fn = self._program(
                "agg_packed_fold", sig,
                lambda: jax.jit(fold_pk) if self.jit else fold_pk)
            final_fn = self._program(
                "agg_packed_final", sig,
                lambda: jax.jit(final_pk) if self.jit else final_pk)
            acc = None
            for p in self._pages(source):
                if acc is None:
                    acc = p
                    self._account("agg_accumulator", acc, node)
                else:
                    acc = fold_fn(acc, p)
            if acc is None:
                return self._groupid_empty_fixup(
                    node, Page.empty(node.output_types, max(mg, 1)))
            out = final_fn(acc)
            return self._groupid_empty_fixup(node, out)

        # global aggregation and remaining exact-capacity shapes:
        # fixed-capacity running fold — pages are already as tight as the
        # key domain allows, so compaction buys nothing
        def fold(acc: Optional[Page], p: Page) -> Page:
            with jax.named_scope("op:Aggregation"):
                cand = p if acc is None else concat_pages_device([acc, p])
                return merge_aggregate(cand, num_keys, aggs, mg,
                                       key_domains=kd, mode="partial")

        def final(acc: Page) -> Page:
            with jax.named_scope("op:Aggregation"):
                return merge_aggregate(acc, num_keys, aggs, mg,
                                       key_domains=kd, mode="single")

        _named(fold, "agg_fold")
        _named(final, "agg_final")

        sig = (num_keys, tuple(aggs), mg, tuple(kd or ()))
        fold_fn = self._program(
            "agg_fold", sig, lambda: jax.jit(fold) if self.jit else fold)
        final_fn = self._program(
            "agg_final", sig, lambda: jax.jit(final) if self.jit else final)

        # seed the first fold with a dead-rows accumulator so EVERY
        # call has the steady-state (acc, page) shape — a bare first
        # call traced a second program (fold of the page alone) per
        # aggregation.  Dictionary-carrying states keep the unseeded
        # start: an empty block's dictionary is None and concat would
        # adopt it.
        seedable = all(c.dictionary is None for c in source.channels)
        acc: Optional[Page] = None
        for p in self._pages(source):
            if acc is None:
                seed = Page.empty(source.output_types, mg) if seedable \
                    else None
                acc = fold_fn(seed, p)
                self._account("agg_accumulator", acc, node)
            else:
                acc = fold_fn(acc, p)
        if acc is None:
            if not node.group_exprs:
                # global aggregation over zero input pages still emits
                # its one row (count 0, other aggregates NULL) — the
                # SQL empty-input contract
                empty = Page.empty(node.source.output_types, 1)
                return grouped_aggregate(empty, [], list(node.aggs), 1,
                                         mode="single")
            return self._groupid_empty_fixup(node, Page.empty(node.output_types, max(mg, 1)))
        out = final_fn(acc)
        self._check_overflow(node, out, mg)
        return self._groupid_empty_fixup(node, out)

    def _groupid_empty_fixup(self, node: AggregationNode, out: Page) -> Page:
        """GROUPING SETS over empty input: sets with no keys (the ()
        set of ROLLUP/CUBE) must still emit their one global-aggregate
        row (count=0, other aggregates NULL) — grouped hashing alone
        produces nothing from nothing."""
        src = node.source
        if not isinstance(src, GroupIdNode):
            return out
        empty_gids = [gid for gid, m in enumerate(src.set_masks) if not any(m)]
        if not empty_gids:
            return out
        if int(host_read(out.num_rows(), "live_count")) > 0:
            return out
        nkeys = len(node.group_exprs) - 1  # last group expr is $group_id
        types = node.output_types
        k = len(empty_gids)
        cols, valids = [], []
        for i, t in enumerate(types):
            if i < nkeys:
                cols.append(np.zeros((k,) + t.value_shape, t.np_dtype))
                valids.append(np.zeros(k, np.bool_))
            elif i == nkeys:
                cols.append(np.asarray(empty_gids, t.np_dtype))
                valids.append(np.ones(k, np.bool_))
            else:
                agg = node.aggs[i - nkeys - 1]
                cols.append(np.zeros((k,) + t.value_shape, t.np_dtype))
                valids.append(
                    np.full(k, agg.fn in ("count", "count_star"), np.bool_)
                )
        dicts = [c.dictionary for c in node.channels]
        return Page.from_arrays(cols, types, valids=valids, dictionaries=dicts)

    def _check_overflow(self, node: AggregationNode, out: Page, mg: int) -> None:
        if not node.group_exprs or self._exact_capacity(node, mg):
            return
        live = int(host_read(out.num_rows(), "live_count"))
        if live >= mg and mg < MAX_AGG_GROUPS:
            self._agg_overrides[node] = mg * 2
            raise GroupCapacityExceeded(mg * 2, node)
