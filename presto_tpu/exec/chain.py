"""A streaming chain, lowered once.

The executor fuses every streaming chain of a plan (scan -> filter ->
project -> join probe -> partial aggregation) into one program
``fn(page, consts)`` per split (``exec/local.py``).  What a chain is,
what its program computes, what the registry keys it by and what XLA
calls it are all read from ONE description, the :class:`Chain` that
:func:`lower_chain` makes in the only walk over the chain grammar
(:func:`_member`):

- a chain is linear: a leaf (a scan, or a breaker whose pages stream
  in) and the ordered :class:`Stage` s over it, leaf first;
- a stage is ``(kind, params, node)``.  ``params`` is a NamedTuple of
  everything the stage's program depends on and ``apply`` its program
  over one page.  ``apply`` receives the params and nothing else, so a
  value cannot be baked into a program without being in its signature:
  two chains that sign equal compute the same function, by
  construction;
- ``node`` is kept for timing (``LocalRunner._time_chain``) and for the
  build sides (:attr:`Chain.joins`); it is never part of the signature.

Input-page schemas are not part of a signature either: they ride as
jit-static pytree aux data (types + dictionaries) and key jit's own
trace cache.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.expr.ir import AggCall, Expr
from presto_tpu.ops.aggregate import (
    agg_exprs, grouped_aggregate, limb_sum_site, one_lane_sums,
)
from presto_tpu.ops.filter_project import (
    compact_page, filter_page, project_page,
)
from presto_tpu.ops.join import probe_fetch, probe_join, probe_lookup
from presto_tpu.page import Block, Page
from presto_tpu.planner.plan import (
    AggregationNode,
    CrossSingleNode,
    FilterNode,
    JoinNode,
    PlanNode,
    PrecomputedNode,
    ProjectNode,
    TableScanNode,
)
from presto_tpu.types import INTEGER


def is_streaming_join(node: JoinNode) -> bool:
    """True when the probe is row-aligned (jittable in a chain):
    semi/anti (presence tests) or unique-key builds. FULL joins always
    take the materializing path — the unmatched-build tail needs
    cross-page match state."""
    if node.kind == "full":
        return False
    return node.kind in ("semi", "anti", "mark") or node.unique_build


def streams(node: JoinNode) -> bool:
    """``is_streaming_join`` for a join that is no index join: an index
    join must not fuse into a chain, whose builder would materialize
    the full build scan instead of point lookups.  What ``lower_chain``
    asks where no runner has demoted a join (``LocalRunner._streaming``
    adds that)."""
    return is_streaming_join(node) and not node.use_index


def cross_append_single(q: Page, r: Page) -> Page:
    """Append a single-row page's columns to every row of ``q`` (the
    cross-join-with-scalar-subquery kernel, EnforceSingleRow +
    NestedLoopJoin's one-row case)."""
    blocks = list(q.blocks)
    for b in r.blocks:
        blocks.append(
            Block(
                jnp.broadcast_to(b.data[0], (q.capacity,) + b.data.shape[1:]),
                jnp.broadcast_to(b.valid[0] & r.row_mask[0], (q.capacity,)),
                b.type,
                b.dictionary,
            )
        )
    return Page(tuple(blocks), q.row_mask)


# ---------------------------------------------------------------------------
# the stage kinds: params, how a plan node gives them, the program
# ---------------------------------------------------------------------------
# ``of(node, max_groups, env)`` reads a member node; ``apply(page,
# consts, build_key, probe)`` is the stage over one page, under the
# operator's scope (the host spans' names: what a device trace books
# the time to).  ``probe`` is the ordinal of the stage's probe in its
# chain, counted from the leaf (``Chain.fn`` counts it as it counts the
# builds): the probing stages open ``probe:<i>`` inside ``op:Join``, so
# a trace tells the probes of one chain apart.  A scope is metadata:
# it is no part of a signature and of no program's text.
#
# ``env`` is the proven interval of each channel of the stage's input
# page (``analysis.kernel_soundness.channel_values`` of the node's
# source; None: no proof is made).  A stage that compiles expressions
# signs what the intervals PROVED, never their numbers: ``proven``, one
# outcome per guarded arithmetic site in ``analysis.ranges.arith_sites``
# order (True = compiled without its runtime guard; ``()`` = no proof
# was made, every guard stays: the program before there were proofs).
# Two tables whose domains differ and prove the same share a program.


def _prove(exprs, env) -> Tuple[bool, ...]:
    if env is None:
        return ()
    from presto_tpu.analysis.ranges import prove_sites

    return prove_sites(exprs, env)


class Filter(NamedTuple):
    predicate: Expr
    proven: Tuple[bool, ...] = ()

    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls(node.predicate, _prove([node.predicate], env))

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope("op:Filter"):
            return filter_page(page, self.predicate, self.proven)


class Project(NamedTuple):
    projections: Tuple[Expr, ...]
    proven: Tuple[bool, ...] = ()

    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls(tuple(node.projections), _prove(node.projections, env))

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope("op:Project"):
            return project_page(page, list(self.projections), self.proven)


class AggPartial(NamedTuple):
    group_exprs: Tuple[Expr, ...]
    aggs: Tuple[AggCall, ...]
    max_groups: int  # resolved: key domains and capacity retries applied
    key_domains: tuple
    presorted: bool
    proven: Tuple[bool, ...] = ()
    #: per aggregate: None = no limb sum of a short addend there; else
    #: what the addend's interval proves of a page's sum: the page
    #: capacity (a power of two, ``ranges.sum_lane_rows``) up to which
    #: it stays inside one int64 lane, 0 = no proof.  A capacity, since
    #: a page's is not known before it arrives; what the aggregation
    #: does with it is ``ops/aggregate.one_lane_sums``
    lane_rows: Tuple[Optional[int], ...] = ()

    @classmethod
    def of(cls, node, max_groups, env=None):
        proven, lane_rows = (), ()
        if env is not None:
            from presto_tpu.analysis.ranges import eval_expr, sum_lane_rows

            proven = _prove(agg_exprs(node.group_exprs, node.aggs), env)
            lanes = tuple(
                sum_lane_rows(eval_expr(a.arg, env))
                if limb_sum_site(a) else None for a in node.aggs)
            if any(r is not None for r in lanes):
                lane_rows = lanes
        return cls(tuple(node.group_exprs), tuple(node.aggs),
                   max_groups(node), tuple(node.key_domains),
                   bool(node.presorted), proven, lane_rows)

    def sums(self) -> Tuple[bool, ...]:
        """One outcome per limb sum of a short addend: True = one int64
        lane for every page its proof covers (``lane_rows``)."""
        one = one_lane_sums(self.aggs, self.lane_rows,
                            self.max_groups if self.group_exprs else 1)
        return tuple(o for o, r in zip(one, self.lane_rows)
                     if r is not None)

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope("op:Aggregation"):
            return grouped_aggregate(
                page, list(self.group_exprs), list(self.aggs),
                self.max_groups, key_domains=list(self.key_domains),
                mode="partial", presorted=self.presorted,
                proven=self.proven, lane_rows=self.lane_rows,
            )


class Probe(NamedTuple):
    left_keys: Tuple[Expr, ...]
    key_domains: tuple
    kind: str
    null_safe: bool
    null_aware: bool
    build_arity: int

    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls(tuple(node.left_keys), tuple(node.key_domains or ()),
                   node.kind, node.null_safe_keys,
                   getattr(node, "null_aware", False),
                   len(node.right.channels))

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope(f"op:Join/probe:{probe}"):
            return probe_join(
                consts[build_key], page, list(self.left_keys),
                key_domains=list(self.key_domains), kind=self.kind,
                build_output=list(range(self.build_arity)),
                null_safe=self.null_safe, null_aware=self.null_aware,
            )


class Lookup(NamedTuple):
    """Not a plan node's: where a chain compacts inside an inner probe
    of a unique build (``_compact_at``), the probe runs as its two
    halves with the compaction between them.  This is the first,
    ``ops/join.probe_lookup`` over the whole page: the rows that found
    their key stay live, and the candidate position in the build rides
    with them as one more (int32) column, the page's last, for
    :class:`Fetch` to take off again."""

    left_keys: Tuple[Expr, ...]
    key_domains: tuple
    null_safe: bool

    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls(tuple(node.left_keys), tuple(node.key_domains or ()),
                   node.null_safe_keys)

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope(f"op:Join/probe:{probe}"):
            pos, match, _ = probe_lookup(
                consts[build_key], page, list(self.left_keys),
                key_domains=list(self.key_domains), null_safe=self.null_safe)
            at = Block(pos.astype(jnp.int32), match, INTEGER)
            return Page(tuple(page.blocks) + (at,), match)


class Fetch(NamedTuple):
    """The second half of the probe :class:`Lookup` began, over the
    small page: every live row matched, so the probe is an inner
    ``ops/join.probe_fetch`` at the position column."""

    build_arity: int

    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls(len(node.right.channels))

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope(f"op:Join/probe:{probe}"):
            *blocks, at = page.blocks
            return probe_fetch(
                consts[build_key], Page(tuple(blocks), page.row_mask),
                at.data, page.row_mask, "inner",
                list(range(self.build_arity)))


class Cross1(NamedTuple):
    @classmethod
    def of(cls, node, max_groups, env=None):
        return cls()

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope("op:CrossSingle"):
            return cross_append_single(page, consts[build_key])


class Compact(NamedTuple):
    """Not a plan node's: ``lower_chain`` places it (``_compact_at``).
    The first ``capacity >> k`` live rows of the page move to a page of
    that capacity, and every stage after it runs over the small page.
    Its ``apply`` also returns the page's live count: a chain that
    compacts is ``fn(page, consts) -> (page, over)``, ``over`` a device
    scalar saying the page held more live rows than fitted.  The answer
    is then of the rows that fitted only, and the caller must not use
    it (``LocalRunner._chain_pages`` raises after the last split, and
    the aggregation that was consuming starts again over the chain that
    does not compact)."""

    k: int

    def apply(self, page, consts, build_key, probe):
        with jax.named_scope("op:Filter"):
            return compact_page(page, max(page.capacity >> self.k, 1))


#: stage kind -> its params class: the fields are the signature, the
#: ``apply`` the program
KINDS = {"filter": Filter, "project": Project, "agg_partial": AggPartial,
         "probe": Probe, "cross1": Cross1, "compact": Compact,
         "lookup": Lookup, "fetch": Fetch}

#: the kinds whose stage is the last to read a build side,
#: ``consts["build_<i>"]``: a ``lookup`` reads the build of the
#: ``fetch`` behind it
_BUILDS = ("probe", "cross1", "fetch")
#: the kinds that end a probe (a ``lookup`` is the first half of the
#: ``fetch`` behind it and shares its ordinal): what ``Chain.probes``
#: counts and the scope ``probe:<i>`` numbers
_PROBES = ("probe", "fetch")

# what XLA would call a program that nothing names (``local._named``
# names every chain the registry holds): after its outermost stage
_UNNAMED = {"filter": "filter_stage", "project": "project_stage",
            "agg_partial": "agg_stage", "probe": "probe_stage",
            "cross1": "cross_stage", "lookup": "probe_stage"}


@dataclasses.dataclass(frozen=True)
class Stage:
    kind: str
    params: tuple
    node: Optional[PlanNode] = dataclasses.field(default=None, compare=False)


def _member(node: PlanNode, streaming) -> Optional[Tuple[str, PlanNode]]:
    """THE chain grammar: (stage kind, the source that streams into it)
    of a chain member, None of anything else: a chain's leaf."""
    if isinstance(node, FilterNode):
        return "filter", node.source
    if isinstance(node, ProjectNode):
        return "project", node.source
    if isinstance(node, AggregationNode) and node.step == "partial":
        return "agg_partial", node.source
    if isinstance(node, JoinNode) and streaming(node):
        return "probe", node.left  # probe side streams
    if isinstance(node, CrossSingleNode):
        return "cross1", node.left
    return None


def chain_leaf(node: PlanNode, streaming=streams) -> PlanNode:
    """Where the chain rooted at ``node`` reads its pages: a scan, or a
    breaker (``node`` itself where it is no chain member)."""
    while True:
        member = _member(node, streaming)
        if member is None:
            return node
        node = member[1]


@dataclasses.dataclass(frozen=True)
class Chain:
    leaf: PlanNode
    stages: Tuple[Stage, ...]  # leaf first

    @property
    def joins(self) -> List[PlanNode]:
        """The nodes whose build side a stage reads, in ``build_<i>``
        order."""
        return [s.node for s in self.stages if s.kind in _BUILDS]

    @property
    def compacts(self) -> bool:
        return any(s.kind == "compact" for s in self.stages)

    @property
    def probes(self) -> int:
        """The probes this chain runs in a row over each page (a probe
        that compacts between its halves is one)."""
        return sum(s.kind in _PROBES for s in self.stages)

    def arith_counts(self) -> Tuple[int, int]:
        """(checked, proven): the guarded arithmetic sites and the limb
        sums of short addends this chain compiles with and without
        their runtime guard (a sum's guard is its row-by-row limb
        split).  A sum counts as proven for the pages its proof covers
        (``AggPartial.lane_rows``, 2^26 rows for TPC-H q1's widest): a
        larger page would split row by row all the same.  A stage
        lowered without intervals counts nothing: it made no proof."""
        checked = proven = 0
        for s in self.stages:
            p = s.params
            sites = list(getattr(p, "proven", ()))
            if isinstance(p, AggPartial):
                sites += p.sums()
            proven += sum(sites)
            checked += len(sites) - sum(sites)
        return checked, proven

    def signature(self, upto: Optional[int] = None) -> tuple:
        """What the program of the first ``upto`` stages (all of them:
        None) depends on, leaf first."""
        return (("leaf",),) + tuple(
            (s.kind,) + tuple(s.params) for s in self.stages[:upto])

    def name(self, upto: Optional[int] = None) -> str:
        """The program's name: the stage kinds leaf first, an
        aggregation tagged with its counts of keys and aggregates
        (``chain_leaf_filter_agg_k2a8`` is TPC-H q1's), a compaction as
        ``compact`` where it happens in front of a probe
        (``chain_leaf_filter_compact_probe_agg_k0a2`` is q14's).  A
        probe that compacts between its halves is still one ``probe``,
        and which it is comes last
        (``chain_leaf_filter_probe_agg_k3a1_compact_in_probe0`` is
        q3's: the benchmark's tests know q3's program by the head of
        its old name, and no PR but a ``benchmark`` one may change
        them).  Of the structure only: the name is part of the
        persistent compile cache's key (``local._named``)."""
        tags, probes, inside = ["leaf"], 0, None
        for s in self.stages[:upto]:
            if s.kind == "agg_partial":
                tags.append(f"agg_k{len(s.params.group_exprs)}"
                            f"a{len(s.params.aggs)}")
            elif s.kind == "lookup":
                inside = probes
            elif s.kind == "fetch":
                tags.append("probe")
            elif s.kind != "compact" or inside is None:
                tags.append(s.kind)
            probes += s.kind in _PROBES
        if inside is not None:
            tags.append(f"compact_in_probe{inside}")
        return "chain_" + "_".join(tags)

    def fn(self, upto: Optional[int] = None) -> Callable:
        """``fn(page, consts) -> page`` of the first ``upto`` stages
        (all of them: None), the identity over a bare leaf;
        ``-> (page, over)`` where one of them compacts
        (:class:`Compact`)."""
        # (params, build key) only: the registry keeps this closure for
        # the life of the process, and a Stage would pin its plan node
        # (and through it the whole plan) behind it
        stages = self.stages[:upto]
        steps, builds, probes = [], 0, 0
        for s in stages:
            steps.append((s.params, f"build_{builds}", probes))
            builds += s.kind in _BUILDS
            probes += s.kind in _PROBES

        def run(page, consts):
            live = None
            for params, key, probe in steps:
                out = params.apply(page, consts, key, probe)
                if isinstance(params, Compact):
                    page, live = out
                    cap_out = page.capacity
                else:
                    page = out
            return page if live is None else (page, live > cap_out)

        if stages:
            run.__name__ = run.__qualname__ = (
                "compact_stage" if any(s.kind == "compact" for s in stages)
                else _UNNAMED[stages[-1].kind])
        return run


def lower_chain(root: PlanNode, *, max_groups: Callable[[AggregationNode], int],
                streaming: Callable[[JoinNode], bool] = streams,
                compact_k: Optional[int] = None,
                intervals: Optional[Callable[[PlanNode], list]] = None
                ) -> Chain:
    """The chain rooted at ``root``.  ``streaming`` says which joins
    probe inside a chain and ``max_groups`` resolves a partial
    aggregation's capacity: what ``LocalRunner._streaming`` (a join
    whose build spilled is demoted) and ``LocalRunner._max_groups``
    (a capacity retry raises it) answer.  ``compact_k`` sets the
    compaction's k, at the place the estimates chose, instead of
    theirs (0: never compact), for tests, for a chain whose compaction
    missed and for callers that take pages only.  ``intervals(node)``
    gives the proven interval of each output channel of a plan node
    (``analysis.kernel_soundness.channel_values`` under the caller's
    memo); a stage is handed its source's, and without it no stage
    proves anything (every guard stays)."""
    stages: List[Stage] = []
    node = root
    while True:
        member = _member(node, streaming)
        if member is None:
            break
        kind, source = member
        env = intervals(source) if intervals is not None else None
        stages.append(Stage(kind, KINDS[kind].of(node, max_groups, env), node))
        node = source
    stages.reverse()
    at = _compact_at(node, stages, compact_k)
    if at is not None:
        i, k, inside = at
        compact = Stage("compact", Compact(k))
        if inside:
            # the probe as its two halves around the compaction; the
            # second keeps the node: the build side, and the time
            # ``_time_chain`` books to the join
            probe = stages[i].node
            stages[i:i + 1] = [
                Stage("lookup", Lookup.of(probe, max_groups)), compact,
                Stage("fetch", Fetch.of(probe, max_groups), probe)]
        else:
            stages.insert(i, compact)
    return Chain(node, tuple(stages))


#: the smallest k worth a compaction, by where it sits: in front of a
#: probe it saves the probe's five gathers a dead slot (PERF.md,
#: PR 26); inside one, the fetch's gathers and, since the chain ends in
#: a partial aggregation, a sort and the group side of an aggregation
#: (PERF.md, PR 31: a q3 page takes 283 ms whole, 269 at k = 1, 156 at
#: k = 2, 63 at k = 3; a miss costs the try and the whole pass again)
COMPACT_MIN_K = {False: 3, True: 2}


def _compact_k(share: float, floor: int = COMPACT_MIN_K[False]) -> int:
    """The k of ``_compact_at`` for an estimated live share: the
    largest with ``2 * share <= 2**-k``, or 0 (no compaction) below
    ``floor``.  The factor of two is room for the estimate; a chain
    one of whose pages still holds more runs again, uncompacted."""
    k = 0
    while k < 30 and 2.0 * share * (2 << k) <= 1.0:
        k += 1
    return k if k >= floor else 0


def _compact_at(leaf: PlanNode, stages: List[Stage],
                compact_k: Optional[int]) -> Optional[Tuple[int, int, bool]]:
    """Where the chain compacts its page and how far: ``(i, k,
    inside)``, the live rows moved to a page of ``capacity >> k`` in
    front of the probe ``stages[i]`` or, ``inside``, between its lookup
    and its fetch; or None.

    One compaction a chain, at the first place that qualifies, leaf
    first; at a probe, in front of it before inside it:

    - in front of a probe with a FilterNode before it since the leaf
      or the probe before: k from the filters' estimated share of
      their source's rows, at least 3;
    - inside an ``inner`` probe of a unique build (the row-aligned
      join that emits build columns; ``left`` keeps every row, ``semi``
      / ``anti`` / ``mark`` fetch nothing): k from the join's estimated
      rows over the leaf scan's, at least 2.  The rows that found no
      key are dropped after the lookup, before anything is fetched for
      them.

    k is the largest for which twice the share fits ``2**-k``
    (``_compact_k``).  Only a chain over a table scan that ends in a
    partial aggregation compacts: after a miss the consumer has to
    start again and the source has to be read again
    (``LocalRunner._chain_pages``).  The answer is a function of the
    plan and the catalog's column metadata alone, never of what a run
    observed: a served statement must find its program compiled."""
    if compact_k == 0 or not (
            stages and stages[-1].kind == "agg_partial"
            and isinstance(leaf, TableScanNode)):
        return None
    from presto_tpu.planner.stats import StatsCalculator

    calc = StatsCalculator()  # memoized: one walk of the plan for all
    for i, s in enumerate(stages):
        if s.kind != "probe":
            continue
        places = [(False, _filtered_share(s.node.left, calc))]
        if s.params.kind == "inner":
            places.append((True, _estimated_share(s.node, leaf, calc)))
        for inside, share in places:
            if share is None:
                continue
            k = _compact_k(share, COMPACT_MIN_K[inside])
            if k:
                return i, compact_k or k, inside
    return None


def _estimated_share(node: PlanNode, of: PlanNode, calc) -> Optional[float]:
    """The textbook estimate (``calc``: a ``StatsCalculator`` without
    history) of ``node``'s rows as a share of ``of``'s; None where the
    estimate would have to read a materialized page."""
    below = [node]
    while below:
        n = below.pop()
        if isinstance(n, PrecomputedNode):
            return None
        below.extend(n.sources)
    rows = calc.rows(of)
    return calc.rows(node) / rows if rows > 0 else None


def _filtered_share(node: PlanNode, calc) -> Optional[float]:
    """The estimated share of their source's rows that the filters at
    the top of ``node`` keep; None without a filter there."""
    source, filtered = node, False
    while isinstance(source, (FilterNode, ProjectNode)):
        filtered = filtered or isinstance(source, FilterNode)
        source = source.source
    return _estimated_share(node, source, calc) if filtered else None
