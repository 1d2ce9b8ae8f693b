"""Plan fragmenter: splits a plan into distributable fragments at
exchange boundaries, with partitioning handles and the
broadcast-vs-repartition join decision.

Reference analog: ``sql/planner/PlanFragmenter.java:84`` (SubPlan tree
of PlanFragments), ``sql/planner/SystemPartitioningHandle.java:58-66``
(SINGLE / FIXED_HASH / FIXED_BROADCAST / SOURCE), the physical
distribution pass ``optimizations/AddExchanges.java:738`` and the CBO
rule ``iterative/rule/DetermineJoinDistributionType.java:33``
(broadcast small build sides, repartition large ones).

TPU framing: a fragment is one SPMD region — its operators fuse into a
single ``shard_map``'d XLA program per wave; fragment boundaries are
the collectives (``all_to_all`` for FIXED_HASH, ``all_gather``/
replication for BROADCAST, host gather for SINGLE).  The fragmenter is
the single source of truth the distributed runner consults for join
distribution modes.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from presto_tpu.planner.plan import (
    AggregationNode,
    CrossSingleNode,
    FilterNode,
    GroupIdNode,
    JoinNode,
    LimitNode,
    OutputNode,
    PlanNode,
    PrecomputedNode,
    ProjectNode,
    SortNode,
    TableScanNode,
    TopNNode,
    UnionNode,
    UnnestNode,
    ValuesNode,
    WindowNode,
)

# Partitioning handle kinds (SystemPartitioningHandle.java:58-66; the
# colocated kind is the bucket-aligned no-exchange placement the
# reference expresses via connector partitioning handles)
SINGLE = "SINGLE"
FIXED_HASH = "FIXED_HASH"
BROADCAST = "BROADCAST"
SOURCE = "SOURCE"
COLOCATED = "COLOCATED"

# Build sides at or below this estimated row count replicate to every
# device (join_distribution_type=AUTOMATIC's size cutoff; the reference
# default is a byte threshold, join-max-broadcast-table-size)
DEFAULT_BROADCAST_THRESHOLD = 1 << 16


@dataclasses.dataclass
class Partitioning:
    kind: str
    keys: Tuple = ()  # key exprs for FIXED_HASH

    def __str__(self) -> str:
        if self.kind == FIXED_HASH and self.keys:
            return f"{self.kind}({len(self.keys)} keys)"
        return self.kind


def _keys_str(keys) -> str:
    """Human-readable partition-key list for EXPLAIN's exchange edges."""
    from presto_tpu.expr.ir import ColumnRef

    names = []
    for k in keys:
        if isinstance(k, ColumnRef):
            names.append(k.name or f"#{k.index}")
        else:
            names.append(str(k))
    return ",".join(names)


@dataclasses.dataclass
class Fragment:
    """One distributable unit (PlanFragment analog): ``root``'s subtree
    down to (but excluding) child-fragment boundaries."""

    fid: int
    root: PlanNode
    distribution: Partitioning  # how this fragment's work is spread
    output: Partitioning  # how its output reaches the parent
    children: List["Fragment"] = dataclasses.field(default_factory=list)
    # per-shard row bound from a TopN/Limit consumer (CreatePartialTopN)
    shard_bound: Optional[int] = None
    # exchange kind on the edge to the parent (hash / gather / merge /
    # broadcast); None derives from the output partitioning
    exchange_kind: Optional[str] = None
    exchange_keys: Tuple = ()

    def exchange_str(self) -> str:
        """The stage-edge exchange EXPLAIN prints: how this fragment's
        pages travel to the consumer (streaming page exchange kinds)."""
        kind = self.exchange_kind
        if kind is None:
            kind = {FIXED_HASH: "hash", BROADCAST: "broadcast",
                    COLOCATED: "colocated"}.get(self.output.kind, "gather")
        keys = self.exchange_keys or (
            self.output.keys if kind == "hash" else ())
        return f"{kind}[{_keys_str(keys)}]" if keys else kind

    def tree_str(self, indent: int = 0) -> str:
        pad = "  " * indent
        bound = "" if self.shard_bound is None \
            else f" shard_bound={self.shard_bound}"
        # stats-calculator row estimate on the stage edge: what the
        # planner believes travels over this exchange (estimate-vs-
        # actual closes the loop in EXPLAIN ANALYZE; this is the est
        # half at fragment granularity)
        try:
            est = estimate_rows(self.root)
        except Exception:
            est = None
        est_s = "" if est is None else f" ~{est} rows"
        lines = [
            f"{pad}Fragment {self.fid} [{self.distribution}] "
            f"=> output [{self.output}] via {self.exchange_str()} "
            f"root={type(self.root).__name__}"
            f"{bound}{est_s}"
        ]
        for ch in self.children:
            lines.append(ch.tree_str(indent + 1))
        return "\n".join(lines)


_SHARED_CALC = None


def estimate_rows(node: PlanNode, calc=None) -> Optional[int]:
    """Row-count estimate via the shared stats calculator
    (planner/stats.py — cost/StatsCalculator.java analog), so the
    broadcast-vs-partitioned distribution decision and the binder's join
    ordering act on the same numbers. A process-wide calculator memoizes
    across the repeated per-join calls of a fragmentation pass (safe:
    the memo holds node references, so recycled ids can't alias)."""
    if isinstance(node, PrecomputedNode):
        return None
    global _SHARED_CALC
    if calc is None:
        if _SHARED_CALC is None:
            from presto_tpu.planner.stats import StatsCalculator

            _SHARED_CALC = StatsCalculator()
        calc = _SHARED_CALC
    return int(calc.rows(node))


def build_side_chainable(node: PlanNode) -> bool:
    """True when the build side can wave-scan on the mesh: a streaming
    chain (filter/project/partial-agg/streaming-join probes) rooted at
    a table scan.  ``exec/chain.chain_leaf``'s descent, but for two
    joins: a FULL join with a unique build and an index join stop
    ``chain_leaf`` and not this walk (ROADMAP D9)."""
    if isinstance(node, (FilterNode, ProjectNode)):
        return build_side_chainable(node.source)
    if isinstance(node, AggregationNode) and node.step == "partial":
        return build_side_chainable(node.source)
    if isinstance(node, CrossSingleNode):
        return build_side_chainable(node.left)
    if isinstance(node, JoinNode) and (
        node.kind in ("semi", "anti", "mark") or node.unique_build
    ):
        return build_side_chainable(node.left)
    return isinstance(node, TableScanNode)


def _trace_to_scan_columns(node: PlanNode, keys) -> Optional[Tuple[PlanNode, List[str]]]:
    """Map ColumnRef join keys through filter/pass-through-projection
    chains to (leaf scan, column names); None when any key derives."""
    from presto_tpu.expr.ir import ColumnRef

    remap = None
    cur = node
    while True:
        if isinstance(cur, FilterNode):
            cur = cur.source
        elif isinstance(cur, ProjectNode):
            proj_map = {i: p.index for i, p in enumerate(cur.projections)
                        if isinstance(p, ColumnRef)}
            src_items = (remap.items() if remap is not None else
                         ((i, i) for i in range(len(cur.channels))))
            remap = {o: proj_map[i] for o, i in src_items if i in proj_map}
            cur = cur.source
        else:
            break
    if not isinstance(cur, TableScanNode):
        return None
    names = []
    for k in keys:
        if not isinstance(k, ColumnRef):
            return None
        idx = k.index if remap is None else remap.get(k.index)
        if idx is None or idx >= len(cur.columns):
            return None
        names.append(cur.handle.columns[cur.columns[idx]].name)
    return cur, names


def colocated_join_scans(jnode, catalog) -> Optional[Tuple[PlanNode, PlanNode]]:
    """(probe_scan, build_scan) when both join sides are scan chains of
    compatibly bucketed tables joined exactly on the bucket columns —
    the shuffle-free colocated join (colocated_join session property +
    NodePartitioningManager bucket-to-node alignment in the reference).
    Bucket id = split index on both sides, so the wave scheduler's
    'device d takes split w*n+d' placement already colocates them."""
    if isinstance(jnode, CrossSingleNode) or catalog is None:
        return None
    left = _trace_to_scan_columns(jnode.left, jnode.left_keys)
    right = _trace_to_scan_columns(jnode.right, jnode.right_keys)
    if left is None or right is None:
        return None
    (lscan, lcols), (rscan, rcols) = left, right
    try:
        lconn = catalog.connector(lscan.handle.connector_name)
        rconn = catalog.connector(rscan.handle.connector_name)
    except KeyError:
        return None
    lb = lconn.bucketing(lscan.handle.table) if hasattr(lconn, "bucketing") else None
    rb = rconn.bucketing(rscan.handle.table) if hasattr(rconn, "bucketing") else None
    if lb is None or rb is None:
        return None
    if lb[1] != rb[1] or lb[2] != rb[2]:
        return None  # different alignment or bucket counts
    if lcols != lb[0] or rcols != rb[0]:
        return None  # join keys must be exactly the bucket columns
    return lscan, rscan


def decide_join_distribution(
    jnode, broadcast_threshold: int = DEFAULT_BROADCAST_THRESHOLD,
    catalog=None, forced: str = "AUTOMATIC", allow_colocated: bool = True,
) -> Tuple[str, Optional[int]]:
    """(mode, estimated build rows): 'colocated' joins bucket-aligned
    scans with no exchange at all; 'broadcast' replicates the build to
    every device; 'partitioned' hash-exchanges both sides on the join
    key (DetermineJoinDistributionType.java:33 —
    AUTOMATIC chooses by build size; the session's
    join_distribution_type forces BROADCAST/PARTITIONED).  Build sides
    that can't wave-scan on the mesh downgrade to broadcast — the
    decision here is the single source of truth for both EXPLAIN
    rendering and execution."""
    if isinstance(jnode, CrossSingleNode):
        return "broadcast", 1
    est = estimate_rows(jnode.right)
    if getattr(jnode, "null_aware", False):
        # three-valued IN/NOT IN: the "build holds a NULL key" flag is a
        # whole-relation property, so the build must be replicated — a
        # hash-partitioned build would confine the NULL to one shard
        return "broadcast", est
    if forced == "BROADCAST":
        return "broadcast", est
    chainable = build_side_chainable(jnode.right)
    if forced == "PARTITIONED":
        return ("partitioned" if chainable else "broadcast"), est
    if (allow_colocated and chainable
            and colocated_join_scans(jnode, catalog) is not None):
        return "colocated", est
    if est is None or est <= broadcast_threshold:
        return "broadcast", est
    if not chainable:
        return "broadcast", est
    return "partitioned", est


# ----------------------------------------------------------------------
# Generalized stage decomposition (PlanFragmenter.java:84 analog).
#
# A plan of ANY shape lowers into a DAG of mesh stages: each stage is a
# streaming chain (filter/project/partial-agg/join probes over a scan or
# a materialized intermediate) optionally rooted by a single-step
# aggregation.  Stage results materialize as PrecomputedNode pages that
# feed consuming stages — the role SubPlan/RemoteSourceNode boundaries
# play in the reference.  Glue breakers (sort, window, union, limit,
# unnest) between stages evaluate on the coordinator, mirroring the
# reference's SINGLE-distribution fragments.  The same traversal drives
# execution (parallel/dist.py) and EXPLAIN (TYPE DISTRIBUTED), so what
# EXPLAIN prints is what execution does.
# ----------------------------------------------------------------------

#: breakers the coordinator evaluates between mesh stages once their
#: subtree is fully materialized (SqlQueryScheduler's SINGLE fragments)
GLUE_BREAKERS = (SortNode, TopNNode, LimitNode, WindowNode, UnionNode,
                 UnnestNode)


def chain_distributable(node: PlanNode) -> Optional[str]:
    """None when ``node``'s subtree is a streaming chain the mesh tier
    compiles into one SPMD wave program; otherwise the human-readable
    reason it is not (surfaced by EXPLAIN and the fallback event)."""
    if isinstance(node, (FilterNode, ProjectNode)):
        return chain_distributable(node.source)
    if isinstance(node, AggregationNode) and node.step == "partial":
        return chain_distributable(node.source)
    if isinstance(node, CrossSingleNode):
        return chain_distributable(node.left)
    if isinstance(node, JoinNode):
        if node.kind == "full":
            return "full outer join needs cross-device unmatched-build state"
        if node.use_index:
            return "index join point-lookups do not wave-scan"
        return chain_distributable(node.left)
    if isinstance(node, TableScanNode):
        return None
    if isinstance(node, PrecomputedNode):
        return None
    return f"{type(node).__name__.replace('Node', '')} breaks the streaming chain"


#: a stage whose leaf is a materialized intermediate below this many
#: rows runs on the coordinator instead — scattering a small page over
#: the mesh is pure dispatch overhead (session property
#: distributed_min_stage_rows; 0 forces every stage onto the mesh,
#: which the dryrun/tests use to exercise multi-stage plans)
DEFAULT_MIN_STAGE_ROWS = 1 << 13


def chain_leaf_node(node: PlanNode) -> PlanNode:
    """The probe-spine leaf of a streaming chain (scan or materialized
    intermediate)."""
    while True:
        if isinstance(node, (FilterNode, ProjectNode)):
            node = node.source
        elif isinstance(node, AggregationNode) and node.step == "partial":
            node = node.source
        elif isinstance(node, (JoinNode, CrossSingleNode)):
            node = node.left
        else:
            return node


def _leaf_big_enough(node: PlanNode, min_rows: int) -> bool:
    if min_rows <= 0:
        return True
    leaf = chain_leaf_node(node)
    if not isinstance(leaf, PrecomputedNode):
        return True  # scans always distribute
    if leaf.page is not None:
        # LIVE rows, not padded capacity: per-device merge pages are
        # allocated at group capacity regardless of actual groups
        import numpy as np

        return int(np.asarray(leaf.page.num_rows())) >= min_rows
    est = getattr(leaf, "_est_rows", None)
    if est is not None:
        return est >= min_rows  # EXPLAIN simulation: planner estimate
    return True


def is_agg_stage(node: PlanNode,
                 min_precomputed_rows: int = DEFAULT_MIN_STAGE_ROWS) -> bool:
    """Root of a scan->chain->partial-agg->exchange->final-merge mesh
    stage (the reference's FIXED_HASH aggregation fragment pair)."""
    return (isinstance(node, AggregationNode) and node.step == "single"
            and not any(a.fn == "evaluate_classifier_predictions"
                        for a in node.aggs)  # host-finalized: local only
            and chain_distributable(node.source) is None
            and _leaf_big_enough(node.source, min_precomputed_rows))


def is_chain_stage(node: PlanNode,
                   min_precomputed_rows: int = DEFAULT_MIN_STAGE_ROWS) -> bool:
    """Root of a pure streaming-chain mesh stage (a SOURCE fragment
    whose consumer is the coordinator or a glue breaker).  A bare
    materialized page or literal is not a stage — re-scattering it
    would be a round trip with no work."""
    if isinstance(node, (PrecomputedNode, ValuesNode)):
        return False
    return (chain_distributable(node) is None
            and _leaf_big_enough(node, min_precomputed_rows))


def is_window_stage(node: PlanNode,
                    min_precomputed_rows: int = DEFAULT_MIN_STAGE_ROWS) -> bool:
    """Root of a distributed window stage: a hash exchange on the
    PARTITION BY keys routes every partition's rows to one shard, then
    ``ops/window.py`` runs per shard (the reference's FIXED_HASH
    WindowNode fragment, AddExchanges partitioning on
    ``WindowNode.getPartitionBy``).  Plain column keys only — both
    tiers route by key channel index; an empty PARTITION BY is a
    whole-relation window and stays on the coordinator."""
    from presto_tpu.expr.ir import ColumnRef

    return (isinstance(node, WindowNode)
            and bool(node.partition_exprs)
            and all(isinstance(e, ColumnRef) for e in node.partition_exprs)
            and chain_distributable(node.source) is None
            and _leaf_big_enough(node.source, min_precomputed_rows))


def is_sort_stage(node: PlanNode,
                  min_precomputed_rows: int = DEFAULT_MIN_STAGE_ROWS) -> bool:
    """Root of a distributed ORDER BY: each shard sorts its own rows
    (ops/sort.py inside the stage program) and the coordinator k-way
    merges the pre-sorted runs (ops/merge.py) — MergeOperator.java:45's
    shape.  Small inputs stay coordinator glue: the merge tree would
    cost more than one local sort."""
    return (isinstance(node, SortNode)
            and chain_distributable(node.source) is None
            and _leaf_big_enough(node.source, min_precomputed_rows))


def is_union_stage(node: PlanNode,
                   min_precomputed_rows: int = DEFAULT_MIN_STAGE_ROWS) -> bool:
    """A UNION whose every leg is itself a runnable stage (chain or
    aggregation): the legs execute as concurrent producer stages
    draining into ONE streaming exchange, instead of sequential
    coordinator concatenation."""
    if not isinstance(node, UnionNode) or len(node.inputs) < 2:
        return False
    return all(
        is_agg_stage(leg, min_precomputed_rows)
        or is_chain_stage(leg, min_precomputed_rows)
        for leg in node.inputs)


def remap_union_leg_page(page, offs, channels):
    """Consumer side of the union exchange, shared by both tiers:
    apply leg ``offs``'s dictionary-code offsets and retype blocks to
    the union's output ``channels`` (legs built against different
    varchar dictionaries unify here)."""
    from presto_tpu.page import Block, Page

    blocks = []
    for i, b in enumerate(page.blocks):
        data = b.data + offs[i] if offs[i] else b.data
        blocks.append(Block(data, b.valid, channels[i].type,
                            channels[i].dictionary))
    return Page(tuple(blocks), page.row_mask)


def child_slots(node: PlanNode):
    """(slot, child) edges of the node kinds the decomposition recurses
    through.  Unknown node kinds yield nothing — their subtree stays on
    the coordinator."""
    if isinstance(node, (JoinNode, CrossSingleNode)):
        return [("left", node.left), ("right", node.right)]
    if isinstance(node, UnionNode):
        return [(("inputs", i), s) for i, s in enumerate(node.inputs)]
    if isinstance(node, (FilterNode, ProjectNode, AggregationNode, SortNode,
                         TopNNode, LimitNode, WindowNode, OutputNode,
                         GroupIdNode, UnnestNode)):
        return [("source", node.source)]
    return []


def get_child(node: PlanNode, slot):
    if isinstance(slot, tuple):
        return getattr(node, slot[0])[slot[1]]
    return getattr(node, slot)


def set_child(node: PlanNode, slot, child: PlanNode) -> None:
    if isinstance(slot, tuple):
        getattr(node, slot[0])[slot[1]] = child
        if isinstance(node, UnionNode):
            # merged dictionaries/offsets were computed from the old arms
            node._channels = None
            node._offsets = None
    else:
        setattr(node, slot, child)


def fully_materialized(node: PlanNode) -> bool:
    """Every leaf below ``node`` is an already-materialized page or a
    literal: evaluating the node now (coordinator-side) is exactly what
    the final local run would do, just earlier — which is what lets an
    ancestor stage distribute over its output."""
    if isinstance(node, (PrecomputedNode, ValuesNode)):
        return True
    slots = child_slots(node)
    if not slots:
        return False
    return all(fully_materialized(c) for _, c in slots)


def _parent_fuses(parent: PlanNode, slot) -> bool:
    """True when ``parent`` would include this child edge in its own
    fused chain, so a stage must not be cut here — the outermost chain
    position (whose parent is a breaker or the root) cuts instead."""
    if isinstance(parent, (FilterNode, ProjectNode)) and slot == "source":
        return True
    if isinstance(parent, AggregationNode) and slot == "source":
        return True
    if isinstance(parent, (JoinNode, CrossSingleNode)) and slot == "left":
        return True
    return False


def lower_stages(plan: PlanNode, run_agg, run_chain, eval_glue,
                 splices: list,
                 min_stage_rows: int = DEFAULT_MIN_STAGE_ROWS,
                 run_window=None, run_sort=None, run_union=None):
    """Decompose ``plan`` into mesh stages bottom-up, splicing each
    executed stage's materialization back into the tree.  ``run_agg`` /
    ``run_chain`` execute a stage and return its PrecomputedNode;
    ``eval_glue`` evaluates a fully-materialized glue breaker on the
    coordinator (may return None to leave it in place).  ``run_window``
    / ``run_sort`` / ``run_union`` (optional — a runner that omits one
    keeps the coordinator-glue behavior for that breaker) execute the
    distributed breaker stages: hash-exchanged per-shard windows,
    per-shard sort + coordinator merge, and concurrent UNION legs into
    one exchange.  ``splices`` records (parent, slot, old_child) for
    restoration.  Returns (mesh_stage_count, lowered_root) — glue
    evaluations do not count.

    Simulation (EXPLAIN) passes callbacks that fabricate empty
    PrecomputedNodes instead of executing, walking the identical
    decomposition, so EXPLAIN (TYPE DISTRIBUTED) always describes what
    execution would actually do."""

    def breaker_stage_kind(node) -> Optional[str]:
        if run_window is not None and is_window_stage(node, min_stage_rows):
            return "window"
        if run_sort is not None and is_sort_stage(node, min_stage_rows):
            return "sort"
        if run_union is not None and is_union_stage(node, min_stage_rows):
            return "union"
        return None

    def try_stage(node, bound=None):
        """(spliced PrecomputedNode, stage count) or (None, 0)."""
        if is_agg_stage(node, min_stage_rows):
            return run_agg(node), 1
        kind = breaker_stage_kind(node)
        if kind == "window":
            return run_window(node), 1
        if kind == "sort":
            return run_sort(node), 1
        if kind == "union":
            # one producer stage per leg, all draining one exchange
            return run_union(node), len(node.inputs)
        if is_chain_stage(node, min_stage_rows):
            return run_chain(node, bound), 1
        return None, 0

    def splice(parent, slot, old, new):
        splices.append((parent, slot, old))
        set_child(parent, slot, new)

    def spine_joins(node):
        """Join/cross nodes along a chain's probe spine (their build
        sides are the chain's off-spine inputs)."""
        while True:
            if isinstance(node, (FilterNode, ProjectNode)):
                node = node.source
            elif isinstance(node, AggregationNode) and node.step == "partial":
                node = node.source
            elif isinstance(node, (JoinNode, CrossSingleNode)):
                yield node
                node = node.left
            else:
                return

    def run_stage_at(parent, slot, child) -> int:
        """Execute the stage rooted at ``child``, first lowering any
        breakers hanging off its build sides (a join build containing
        an aggregation subquery distributes as its own stage; build
        splices cannot break the probe chain)."""
        if isinstance(child, UnionNode):
            spines = [leg.source if isinstance(leg, AggregationNode) else leg
                      for leg in child.inputs]
        elif isinstance(child, (AggregationNode, WindowNode, SortNode)):
            spines = [child.source]
        else:
            spines = [child]
        n = 0
        for sp in spines:
            n += sum(lower_edge(j, "right") for j in spine_joins(sp))
        # a TopN/Limit consumer bounds each shard's output to its count
        # before the gather (CreatePartialTopN.java role) — the glue
        # breaker still runs on the coordinator for the global pick
        bound = parent if (isinstance(parent, (TopNNode, LimitNode))
                           and slot == "source") else None
        new, k = try_stage(child, bound)
        assert new is not None  # build splices never un-distribute a chain
        splice(parent, slot, child, new)
        return n + k

    def cuts_here(child, fuses: bool) -> bool:
        """Whether ``child`` roots a stage at this edge: aggregations
        and breaker stages cut regardless of the parent (they never
        fuse into an ancestor chain); a pure chain cuts only at its
        outermost position (fusing parents defer to the ancestor that
        will include this subtree in its own stage)."""
        return (is_agg_stage(child, min_stage_rows)
                or breaker_stage_kind(child) is not None
                or (not fuses and is_chain_stage(child, min_stage_rows)))

    def lower_edge(parent, slot) -> int:
        child = get_child(parent, slot)
        if (isinstance(parent, (JoinNode, CrossSingleNode)) and slot == "right"
                and build_side_chainable(child)):
            # the stage machinery wave-scans chainable build sides
            # itself (sharded/colocated builds); pre-materializing here
            # would downgrade a partitioned build to broadcast
            return 0
        fuses = _parent_fuses(parent, slot)
        if cuts_here(child, fuses):
            return run_stage_at(parent, slot, child)
        n = 0
        for cslot, _ in child_slots(child):
            n += lower_edge(child, cslot)
        if n == 0:
            return 0
        if cuts_here(child, fuses):
            # children materialized: the node became a stage root (e.g.
            # an aggregation whose chain leaf was a subquery, or a
            # window/sort over a now-materialized intermediate)
            return n + run_stage_at(parent, slot, child)
        # a glue breaker over a fully-materialized subtree evaluates on
        # the coordinator so an ANCESTOR stage can distribute over it
        if isinstance(child, GLUE_BREAKERS) and fully_materialized(child):
            new = eval_glue(child)
            if new is not None:
                splice(parent, slot, child, new)
        return n

    class _Holder:
        source = plan

    holder = _Holder()
    n = lower_edge(holder, "source")
    return n, holder.source


def fragment_plan(
    plan: PlanNode, broadcast_threshold: int = DEFAULT_BROADCAST_THRESHOLD,
    catalog=None, min_stage_rows: int = DEFAULT_MIN_STAGE_ROWS,
) -> Fragment:
    """Lower a plan into a SubPlan-style fragment tree by SIMULATING the
    generalized stage decomposition (``lower_stages`` with fabricated
    stage outputs) — the fragment tree is therefore exactly the stage
    DAG the distributed runner would execute (for the min-stage-rows
    cutoff the simulation uses planner ROW ESTIMATES where execution
    sees actual intermediate sizes — the one adaptive decision that can
    differ).  Fragments: a SINGLE coordinator fragment at the root (and
    per glue breaker), a FIXED_HASH merge + SOURCE leaf pair per
    distributed aggregation, SOURCE chain fragments, and one fragment
    per join build side (BROADCAST / FIXED_HASH / COLOCATED by the
    distribution decision)."""
    counter = [0]

    def next_id() -> int:
        fid = counter[0]
        counter[0] += 1
        return fid

    def _leaf_distribution(node: PlanNode) -> Partitioning:
        n = node
        while True:
            if isinstance(n, TableScanNode):
                return Partitioning(SOURCE)
            srcs = n.sources
            if not srcs:
                return Partitioning(SINGLE)
            n = srcs[0]

    def collect_children(node: PlanNode) -> List[Fragment]:
        """Fragments feeding ``node``'s subtree: spliced child-stage
        fragments (tagged on their PrecomputedNodes) and join build
        fragments along streaming chains."""
        out: List[Fragment] = []
        frag = getattr(node, "_frag", None)
        if frag is not None:
            return [frag]
        if isinstance(node, (JoinNode, CrossSingleNode)):
            out += collect_children(node.left)
            mode, _ = decide_join_distribution(
                node, broadcast_threshold, catalog=catalog)
            kind = {"broadcast": BROADCAST, "colocated": COLOCATED}.get(
                mode, FIXED_HASH)
            keys = tuple(getattr(node, "right_keys", ()))
            out.append(Fragment(
                next_id(), node.right,
                distribution=_leaf_distribution(node.right),
                output=Partitioning(kind, keys if kind == FIXED_HASH else ()),
                children=collect_children(node.right),
            ))
            return out
        for _, child in child_slots(node):
            out += collect_children(child)
        return out

    def tag(node: PlanNode, frag: Fragment) -> PrecomputedNode:
        pre = PrecomputedNode(page=None, channel_list=node.channels)
        pre._frag = frag
        try:
            pre._est_rows = estimate_rows(node)
        except Exception:
            pre._est_rows = None
        return pre

    def sim_agg(node: AggregationNode) -> PrecomputedNode:
        keys = tuple(node.group_exprs)
        part = Partitioning(FIXED_HASH, keys) if keys else Partitioning(SINGLE)
        leaf = Fragment(
            next_id(), node.source,
            distribution=_leaf_distribution(node.source), output=part,
            children=collect_children(node.source),
        )
        merge = Fragment(next_id(), node, distribution=part,
                         output=Partitioning(SINGLE), children=[leaf])
        return tag(node, merge)

    def sim_chain(node: PlanNode, bound=None) -> PrecomputedNode:
        frag = Fragment(
            next_id(), node, distribution=_leaf_distribution(node),
            output=Partitioning(SINGLE), children=collect_children(node),
        )
        frag.shard_bound = None if bound is None else bound.count
        return tag(node, frag)

    def sim_glue(node: PlanNode) -> PrecomputedNode:
        frag = Fragment(
            next_id(), node, distribution=Partitioning(SINGLE),
            output=Partitioning(SINGLE), children=collect_children(node),
        )
        return tag(node, frag)

    def sim_window(node: WindowNode) -> PrecomputedNode:
        # source fragment hash-exchanges on the PARTITION BY keys; the
        # window fragment runs per shard and gathers
        keys = tuple(node.partition_exprs)
        part = Partitioning(FIXED_HASH, keys)
        leaf = Fragment(
            next_id(), node.source,
            distribution=_leaf_distribution(node.source), output=part,
            children=collect_children(node.source),
        )
        win = Fragment(next_id(), node, distribution=part,
                       output=Partitioning(SINGLE), children=[leaf])
        return tag(node, win)

    def sim_sort(node: SortNode) -> PrecomputedNode:
        # per-shard sort inside the stage; the edge to the consumer is
        # an order-preserving merge of the pre-sorted runs
        frag = Fragment(
            next_id(), node, distribution=_leaf_distribution(node.source),
            output=Partitioning(SINGLE), children=collect_children(node.source),
            exchange_kind="merge", exchange_keys=tuple(node.sort_exprs),
        )
        return tag(node, frag)

    def sim_union(node: UnionNode) -> PrecomputedNode:
        # one concurrent producer fragment per leg, all draining into
        # the union fragment's single streaming exchange
        legs = []
        for leg in node.inputs:
            legs.append(Fragment(
                next_id(), leg, distribution=_leaf_distribution(leg),
                output=Partitioning(SINGLE), children=collect_children(leg),
            ))
        frag = Fragment(next_id(), node, distribution=Partitioning(SINGLE),
                        output=Partitioning(SINGLE), children=legs,
                        exchange_kind="union")
        return tag(node, frag)

    splices: list = []
    try:
        n, root = lower_stages(plan, sim_agg, sim_chain, sim_glue, splices,
                               min_stage_rows=min_stage_rows,
                               run_window=sim_window, run_sort=sim_sort,
                               run_union=sim_union)
        out = Fragment(
            next_id(), plan, distribution=Partitioning(SINGLE),
            output=Partitioning(SINGLE), children=collect_children(root),
        )
        out.mesh_stages = n  # simulated stage count (FRAGMENTED header)
        return out
    finally:
        for parent, slot, old in reversed(splices):
            set_child(parent, slot, old)


def undistributable_reason(plan: PlanNode) -> str:
    """Why no stage distributes — the loud part of the fallback."""
    node = plan
    while isinstance(node, OutputNode):
        node = node.source
    if isinstance(node, AggregationNode) and node.step == "single":
        return chain_distributable(node.source) or "distributable"
    return chain_distributable(node) or "distributable"


def explain_distributed(
    plan: PlanNode, broadcast_threshold: int = DEFAULT_BROADCAST_THRESHOLD,
    catalog=None, min_stage_rows: int = DEFAULT_MIN_STAGE_ROWS,
) -> str:
    """EXPLAIN (TYPE DISTRIBUTED): the FRAGMENTED header is the loud
    distributed-vs-local signal VERDICT r3 asked for — when execution
    would silently have run locally, the header says so and why."""
    frags = fragment_plan(plan, broadcast_threshold, catalog=catalog,
                          min_stage_rows=min_stage_rows)
    n = frags.mesh_stages
    if n == 0:
        header = (f"FRAGMENTED: no — {undistributable_reason(plan)}; "
                  "plan executes on the coordinator only\n")
    else:
        header = f"FRAGMENTED: yes ({n} mesh stage{'s' if n > 1 else ''})\n"
    return header + frags.tree_str()
