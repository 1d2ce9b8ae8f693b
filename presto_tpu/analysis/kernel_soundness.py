"""Expression-tier kernel-soundness checker.

Runs the :mod:`presto_tpu.analysis.ranges` abstract interpreter over
every compiled expression in a bound plan and reports, with node-level
attribution (reusing the validator's stable ``NodeType#k`` names):

``overflow``
    int / short-decimal ops whose *raw* result interval escapes the
    device lane width the kernel computes in (the wrap point the
    reference's checked bytecode raises ARITHMETIC_OVERFLOW at),
    including aggregation accumulators folded over the row-count
    bounds of :func:`analysis.properties.derive_properties` — the
    SF100 ``sum(l_extendedprice * (1 - l_discount))`` class.

``null-policy``
    every scalar kernel family must declare its mask behavior in
    ``expr.compile.NULL_POLICY`` (strict / preserving / generating —
    the expression-level analogue of ``rules.NULL_MASK_POLICY``), and
    the declaration must agree with this module's *independent*
    structural model (:func:`ranges.null_effect`).  A kernel that
    nulls lanes its declaration doesn't admit (or an undeclared
    kernel) is an error: downstream mask reasoning would be wrong.

``lossy-cast`` / ``division``
    truncating casts reachable with provably out-of-range intervals,
    and divisions whose divisor interval contains zero (lanes NULL at
    runtime where the reference raises DIVISION_BY_ZERO; a *literal*
    zero divisor is an error, a possible one is a warning).

Severity discipline: a finding is an **error** only when backed by
evidence (``AbstractValue.known`` — literals, VALUES rows, zone-map
domains, known row bounds); type-contract-only escapes surface as
warnings at aggregation folds and are silent elsewhere (every int64
add "may" overflow by type bounds alone — flagging that would bury
the real findings).  ``assert_kernel_sound`` raises only on errors, so
the TPC-H/TPC-DS corpus gate stays clean while still proving the
evidence-backed cases.

The same channel-interval propagation feeds the runtime cross-check:
``PRESTO_TPU_RANGE_SANITIZER=1`` (exec/local.py) samples observed
column min/max at page boundaries and fails loudly when a value
escapes its predicted interval — transfer functions must be sound,
not just plausible.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from presto_tpu.analysis import ranges
from presto_tpu.analysis.ranges import AbstractValue, eval_expr, top
from presto_tpu.analysis.rules import Issue, _node_exprs, _walk_exprs
from presto_tpu.analysis.validator import _Context, _walk
from presto_tpu.expr.ir import AggCall, Call, ColumnRef, Expr
from presto_tpu.planner.plan import (
    AggregationNode,
    CrossSingleNode,
    FilterNode,
    GroupIdNode,
    JoinNode,
    LimitNode,
    OutputNode,
    PlanNode,
    ProjectNode,
    SortNode,
    TableScanNode,
    TopNNode,
    UnionNode,
    ValuesNode,
    WindowNode,
)

__all__ = [
    "EvidenceContext",
    "KernelSoundnessError",
    "analyze_kernels",
    "arith_report",
    "assert_kernel_sound",
    "predicted_intervals",
]

_I64_MAX = (1 << 63) - 1

#: to_sum_limbs splits each (hi, lo) pair into 4 base-1e9 digits whose
#: per-digit segment sums stay < 2^63 for ~9.2e9 addends (see
#: ops/decimal128.to_sum_limbs) — row bounds beyond this make even the
#: limb accumulator suspect
_LIMB_SAFE_ROWS = 9_200_000_000


class KernelSoundnessError(Exception):
    """A plan failed kernel-soundness analysis; ``issues`` carries the
    error-severity findings (each naming its node and checker)."""

    def __init__(self, issues: List[Issue]):
        self.issues = list(issues)
        lines = "\n".join(f"  {i}" for i in self.issues)
        super().__init__(
            f"plan failed kernel-soundness analysis ({len(self.issues)} "
            f"issue{'s' if len(self.issues) != 1 else ''}):\n{lines}")


# ---------------------------------------------------------------------------
# channel-interval propagation (bottom-up over the plan DAG)
# ---------------------------------------------------------------------------

class EvidenceContext(_Context):
    """The context code generation is proved under
    (``exec/local.LocalRunner``, one per query).

    A plan outlives the table state it was bound against: plans are
    cached by SQL text, and a scan enumerates its splits when the query
    runs.  So nothing bound into the plan is evidence here.  A scan's
    domains are read from its connector when a chain first asks, and
    only from a connector that versions its tables (``table_version``:
    the domain read between two equal versions is that version's); the
    split count is read BEFORE them and kept in ``splits``, and the
    scan runs over those splits and no later ones, so a row appended
    since is the next statement's (the connector's side of the
    bargain: a declared domain is made true before the rows it covers
    can be counted; docs/static-analysis.md).  Row counts bound
    nothing (``_agg_output_values``): the handle's is the bind's."""

    def __init__(self, catalog):
        super().__init__()
        self._catalog = catalog
        #: id(scan) -> the number of splits its evidence covers
        self.splits: Dict[int, int] = {}

    def channels(self, node: PlanNode) -> list:
        if isinstance(node, TableScanNode) and id(node) not in self._channels:
            self._channels[id(node)] = [
                dataclasses.replace(c, domain=d)
                for c, d in zip(node.channels, self._scan_domains(node))]
        return super().channels(node)

    def _scan_domains(self, node: TableScanNode) -> list:
        conn = self._catalog.connector(node.handle.connector_name)
        version = getattr(conn, "table_version", None)
        domain = getattr(conn, "column_domain", None)
        if version is None or domain is None:
            return [None] * len(node.columns)
        table = node.handle.table
        v = version(table)
        if node.splits is None and hasattr(conn, "num_splits"):
            self.splits[id(node)] = conn.num_splits(table)
        doms = [domain(table, node.handle.columns[i].name)
                for i in node.columns]
        if version(table) != v:  # a write landed in between
            return [None] * len(node.columns)
        return doms



def _scan_values(node: TableScanNode, ctx: _Context) -> List[AbstractValue]:
    out = [ranges.channel_value_of_channel(c) for c in ctx.channels(node)]
    if not node.constraints:
        return out
    by_name = {c.name: i for i, c in enumerate(ctx.channels(node))}
    for col, op, v in node.constraints:
        i = by_name.get(col)
        if i is None:
            continue
        a = out[i]
        # a pushed-down conjunct is evidence: surviving rows satisfy it
        if op == "eq":
            out[i] = AbstractValue(v, v, may_null=False, known=True)
        elif op in ("lt", "le"):
            out[i] = AbstractValue(a.lo, min(a.hi, v), may_null=False,
                                   known=True)
        elif op in ("gt", "ge"):
            out[i] = AbstractValue(max(a.lo, v), a.hi, may_null=False,
                                   known=True)
    return out


def _values_values(node: ValuesNode) -> List[AbstractValue]:
    out = []
    for j, t in enumerate(node.types):
        cells = [r[j] for r in node.rows]
        nums = [c for c in cells if isinstance(c, (int, float))
                and not isinstance(c, bool)]
        has_null = any(c is None for c in cells)
        if nums and len(nums) + sum(c is None for c in cells) == len(cells) \
                and t.value_shape == ():
            out.append(AbstractValue(min(nums), max(nums),
                                     may_null=has_null, known=True))
        else:
            out.append(top(t))
    return out


def _agg_output_values(node: AggregationNode, env: List[AbstractValue],
                       ctx: _Context) -> List[AbstractValue]:
    from presto_tpu.analysis.properties import derive_properties
    from presto_tpu.ops.aggregate import output_type

    keys = [eval_expr(e, env) for e in node.group_exprs]
    try:
        hi_rows = (None if isinstance(ctx, EvidenceContext)
                   else derive_properties(node.source).hi)
    except Exception:
        hi_rows = None
    outs = []
    for agg in node.aggs:
        t = output_type(agg)
        if agg.fn in ("count", "count_star"):
            outs.append(AbstractValue(
                0, hi_rows if hi_rows is not None else ranges.INF,
                may_null=False, known=hi_rows is not None))
        elif agg.fn in ("min", "max", "avg", "arbitrary", "any_value") \
                and agg.arg is not None and t.value_shape == ():
            a = eval_expr(agg.arg, env)
            # min/max/avg outputs lie inside the argument interval
            outs.append(AbstractValue(a.lo, a.hi, may_null=True,
                                      known=a.known))
        elif agg.fn in ("sum", "sum0") and t.value_shape == ():
            a = eval_expr(agg.arg, env)
            m = max(abs(a.lo), abs(a.hi))
            bound = ranges.INF if hi_rows is None else m * hi_rows
            outs.append(AbstractValue(-bound, bound, may_null=True,
                                      known=a.known and hi_rows is not None))
        else:
            outs.append(top(t))
    if node.step == "partial":
        # partial layout is keys + state columns; states are checked by
        # the accumulator rule, not propagated as intervals
        return keys + [top(c.type) for c in ctx.channels(node)[len(keys):]]
    return keys + outs


def channel_values(node: PlanNode, ctx: _Context,
                   memo: Dict[int, List[AbstractValue]]) -> List[AbstractValue]:
    """Per-output-channel abstract values of ``node``, id-memoized.

    Sound over-approximation at every node kind; anything without a
    precise rule falls back to the type contract (assumed, which the
    checkers and the sanitizer both skip)."""
    key = id(node)
    if key in memo:
        return memo[key]
    memo[key] = [top(c.type) for c in ctx.channels(node)]  # cycle guard

    if isinstance(node, TableScanNode):
        vals = _scan_values(node, ctx)
    elif isinstance(node, ValuesNode):
        vals = _values_values(node)
    elif isinstance(node, (FilterNode, LimitNode, SortNode, TopNNode,
                           OutputNode)):
        vals = list(channel_values(node.source, ctx, memo))
        if isinstance(node, OutputNode):
            vals = vals[:len(ctx.channels(node))]
    elif isinstance(node, ProjectNode):
        env = channel_values(node.source, ctx, memo)
        vals = [eval_expr(e, env) for e in node.projections]
    elif isinstance(node, AggregationNode):
        env = channel_values(node.source, ctx, memo)
        vals = _agg_output_values(node, env, ctx)
    elif isinstance(node, GroupIdNode):
        env = channel_values(node.source, ctx, memo)
        keys = [eval_expr(e, env) for e in node.key_exprs]
        # replicas mask inactive keys to NULL
        keys = [AbstractValue(k.lo, k.hi, may_null=True, may_nan=k.may_nan,
                              known=k.known) for k in keys]
        gid = AbstractValue(0, max(len(node.set_masks) - 1, 0),
                            may_null=False, known=True)
        vals = list(env) + keys + [gid]
    elif isinstance(node, JoinNode):
        lv = channel_values(node.left, ctx, memo)
        if node.kind in ("semi", "anti"):
            vals = list(lv)
        elif node.kind == "mark":
            vals = list(lv) + [AbstractValue(0, 1, may_null=True, known=True)]
        else:
            rv = channel_values(node.right, ctx, memo)
            # outer joins null the unmatched side; forcing may_null on
            # every output keeps this sound for all kinds
            vals = [AbstractValue(v.lo, v.hi, True, v.may_nan, v.known)
                    for v in lv + rv]
    elif isinstance(node, CrossSingleNode):
        vals = (list(channel_values(node.left, ctx, memo))
                + list(channel_values(node.right, ctx, memo)))
    elif isinstance(node, UnionNode):
        arms = [channel_values(s, ctx, memo) for s in node.inputs]
        n = min(len(a) for a in arms) if arms else 0
        merged_chans = ctx.channels(node)
        vals = []
        for i in range(n):
            t = merged_chans[i].type if i < len(merged_chans) else None
            if t is not None and t.is_string:
                # dictionary merge re-codes: computed code intervals
                # from the arms don't survive; the merged channel's own
                # domain does
                vals.append(ranges.channel_value_of_channel(merged_chans[i]))
            elif t is None or any(
                    i >= len(ctx.channels(s)) or ctx.channels(s)[i].type != t
                    for s in node.inputs):
                # an arm in another representation (a decimal of
                # another scale): its interval is not the merged lane's
                vals.append(top(t) if t is not None else arms[0][i])
            else:
                v = arms[0][i]
                for a in arms[1:]:
                    v = v.join(a[i])
                vals.append(v)
    elif isinstance(node, WindowNode):
        env = channel_values(node.source, ctx, memo)
        vals = list(env) + [top(f.type) for f in node.funcs]
    else:
        vals = memo[key]  # type contract per channel

    # channel-count mismatches (broken plans) fall back to the contract
    chans = ctx.channels(node)
    if len(vals) != len(chans):
        vals = [top(c.type) for c in chans]
    memo[key] = vals
    return vals


# ---------------------------------------------------------------------------
# checkers
# ---------------------------------------------------------------------------

def _fmt_iv(iv: Tuple[float, float]) -> str:
    lo, hi = iv
    return f"[{lo}, {hi}]"


def _check_exprs(node: PlanNode, ctx: _Context,
                 memo: Dict[int, List[AbstractValue]]) -> List[Issue]:
    issues: List[Issue] = []
    name = ctx.name(node)
    for root, src, label in _node_exprs(node):
        env = channel_values(src, ctx, memo)

        def hazard(kind, e, raw, bounds, known, _label=label):
            if kind == "overflow":
                if not known:
                    return  # type-contract-only escape: see module doc
                issues.append(Issue(
                    "overflow", name,
                    f"{_label}: {e.fn} over {e.type!r} can reach "
                    f"{_fmt_iv(raw)}, outside the device lane "
                    f"{_fmt_iv(bounds)} — lanes NULL at runtime "
                    f"(reference raises ARITHMETIC_OVERFLOW)"))
            elif kind == "division":
                sev = "error" if known else "warning"
                issues.append(Issue(
                    "division", name,
                    f"{_label}: {e.fn} divisor interval {_fmt_iv(raw)} "
                    f"contains zero — lanes NULL at runtime (reference "
                    f"raises DIVISION_BY_ZERO)", severity=sev))
            elif kind == "lossy-cast":
                if not known:
                    return
                issues.append(Issue(
                    "lossy-cast", name,
                    f"{_label}: {e.fn} to {e.type!r} reachable with "
                    f"{_fmt_iv(raw)}, outside {_fmt_iv(bounds)} — "
                    f"out-of-range lanes NULL at runtime (reference "
                    f"raises INVALID_CAST_ARGUMENT)"))

        eval_expr(root, env, hazard)
        issues.extend(_check_null_policy(root, name, label))
    return issues


def _check_null_policy(root: Expr, node_name: str, label: str) -> List[Issue]:
    """Cross-check every Call's declared mask behavior against the
    structural model.  Two independently-maintained tables: the kernel
    author declares (expr.compile.NULL_POLICY), the analyzer models
    (ranges.null_effect); disagreement or a missing declaration is an
    error with node attribution."""
    from presto_tpu.expr.compile import NULL_POLICY

    issues: List[Issue] = []
    seen = set()
    for e, _in_lambda in _walk_exprs(root):
        if not isinstance(e, Call) or e.fn in seen:
            continue
        seen.add(e.fn)
        declared = NULL_POLICY.get(e.fn)
        modeled = ranges.null_effect(e.fn)
        if declared is None:
            issues.append(Issue(
                "null-policy", node_name,
                f"{label}: kernel '{e.fn}' declares no null policy "
                f"(expr.compile.NULL_POLICY); model says '{modeled}'"))
        elif declared != modeled:
            issues.append(Issue(
                "null-policy", node_name,
                f"{label}: kernel '{e.fn}' declares null policy "
                f"'{declared}' but the structural model derives "
                f"'{modeled}' — masks would not flow as declared"))
    return issues


def _check_accumulators(node: AggregationNode, ctx: _Context,
                        memo: Dict[int, List[AbstractValue]]) -> List[Issue]:
    """Fold each sum/avg accumulator's per-row interval over the
    subtree's row-count bound; an int64-lane state that can escape 2^63
    is the silent-wrap class the reference's checked accumulators
    raise on."""
    from presto_tpu.analysis.properties import derive_properties
    from presto_tpu.ops.aggregate import state_types

    if node.step == "final":
        return []  # the partial stage below already checked the fold
    issues: List[Issue] = []
    env = channel_values(node.source, ctx, memo)
    try:
        hi_rows = derive_properties(node.source).hi
    except Exception:
        hi_rows = None
    for i, agg in enumerate(node.aggs):
        if agg.fn not in ("sum", "sum0", "avg"):
            continue
        try:
            st = state_types(agg)[0]
        except Exception:
            continue
        if st.name == "double" or st.name.startswith("interval"):
            continue
        label = f"agg[{i}]"
        a = eval_expr(agg.arg, env)
        if st.is_long_decimal:
            # base-1e9 limb accumulation: sound up to ~9.2e9 addends
            if hi_rows is not None and hi_rows > _LIMB_SAFE_ROWS:
                issues.append(Issue(
                    "overflow", ctx.name(node),
                    f"{label}: {agg.fn} limb accumulator is sound to "
                    f"~{_LIMB_SAFE_ROWS} rows but the subtree bound is "
                    f"{hi_rows}", severity="warning"))
            continue
        m = max(abs(a.lo), abs(a.hi))
        worst = ranges.INF if hi_rows is None else m * hi_rows
        if worst <= _I64_MAX:
            continue
        evidence = a.known and hi_rows is not None
        rows_s = "unbounded" if hi_rows is None else str(hi_rows)
        issues.append(Issue(
            "overflow", ctx.name(node),
            f"{label}: {agg.fn} accumulates {agg.arg.type!r} in "
            f"{st!r} (int64 lanes); per-row magnitude ≤ {m} over "
            f"{rows_s} rows can escape 2^63 and wrap silently "
            f"(reference raises ARITHMETIC_OVERFLOW)",
            severity="error" if evidence else "warning"))
    return issues


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def analyze_kernels(plan: PlanNode) -> List[Issue]:
    """All kernel-soundness diagnostics for ``plan``, bottom-up."""
    from presto_tpu.obs.metrics import METRICS

    ctx = _Context()
    order: List[PlanNode] = []
    _walk(plan, ctx, set(), order)
    memo: Dict[int, List[AbstractValue]] = {}
    issues: List[Issue] = []
    for node in order:
        if ctx.channel_error(node) is not None:
            continue  # the plan validator owns broken-channel reporting
        try:
            issues.extend(_check_exprs(node, ctx, memo))
            if isinstance(node, AggregationNode):
                issues.extend(_check_accumulators(node, ctx, memo))
        except Exception as e:  # a crashing checker is itself a finding
            issues.append(Issue(
                "kernel-soundness", ctx.name(node),
                f"checker crashed: {type(e).__name__}: {e}"))
    n_over = sum(1 for i in issues if i.rule in ("overflow", "lossy-cast",
                                                 "division"))
    n_null = sum(1 for i in issues if i.rule == "null-policy")
    if n_over:
        METRICS.counter("kernel.overflow_hazards").inc(n_over)
    if n_null:
        METRICS.counter("kernel.null_violations").inc(n_null)
    return issues


def assert_kernel_sound(plan: PlanNode) -> List[Issue]:
    """Raise :class:`KernelSoundnessError` on any error-severity
    finding; return the (possibly empty) warning list otherwise."""
    issues = analyze_kernels(plan)
    errors = [i for i in issues if i.severity == "error"]
    if errors:
        raise KernelSoundnessError(errors)
    return [i for i in issues if i.severity != "error"]


def predicted_intervals(plan: PlanNode) -> Dict[int, List[Optional[Tuple]]]:
    """Per-node predicted output intervals for the runtime range
    sanitizer: ``{id(node): [(lo, hi) | None per channel]}``.  Only
    evidence-backed (``known``) finite intervals of scalar integer-lane
    channels are emitted — those are hard predictions a single escaped
    value falsifies; type-contract intervals can't be escaped and float
    lanes have no wrap point."""
    ctx = _Context()
    order: List[PlanNode] = []
    _walk(plan, ctx, set(), order)
    memo: Dict[int, List[AbstractValue]] = {}
    out: Dict[int, List[Optional[Tuple]]] = {}
    for node in order:
        if ctx.channel_error(node) is not None:
            continue
        vals = channel_values(node, ctx, memo)
        chans = ctx.channels(node)
        preds: List[Optional[Tuple]] = []
        for v, c in zip(vals, chans):
            t = c.type
            if (v.known and v.lo != -ranges.INF and v.hi != ranges.INF
                    and t.value_shape == ()
                    and t.name not in ("double", "real")
                    and ranges.device_int_bounds(t) is not None):
                preds.append((int(v.lo), int(v.hi)))
            else:
                preds.append(None)
        out[id(node)] = preds
    return out


def arith_report(plan: PlanNode, ctx: _Context, max_groups) -> List[str]:
    """What the intervals prove for code generation, one line per
    guarded arithmetic site and per limb sum of a short addend:
    ``<node> <label>: <expr> proven|checked`` (EXPLAIN (TYPE
    VALIDATE); ``exec/local.LocalRunner.arith_report`` brings its
    :class:`EvidenceContext` and its ``max_groups(node)``).
    ``proven``: the executor's chains compile the site without its
    runtime guard (``exec/chain.lower_chain`` asks the same
    ``channel_values``, ``ranges.site_proven`` and ``ops/aggregate.
    one_lane_sums``); for a sum, that a page of up to the stated rows
    reduces in one int64 lane."""
    from presto_tpu.ops.aggregate import (
        agg_exprs, limb_sum_site, one_lane_sums,
    )

    order: List[PlanNode] = []
    _walk(plan, ctx, set(), order)
    memo: Dict[int, List[AbstractValue]] = {}
    lines: List[str] = []
    for node in order:
        if ctx.channel_error(node) is not None:
            continue
        for root, src, label in _node_exprs(node):
            env = channel_values(src, ctx, memo)
            exprs = (agg_exprs((), [root]) if isinstance(root, AggCall)
                     else [root])
            for site, ok in zip(ranges.arith_sites(exprs),
                                ranges.prove_sites(exprs, env)):
                lines.append(f"{ctx.name(node)} {label}: {site!r} "
                             f"{'proven' if ok else 'checked'}")
            if isinstance(root, AggCall) and limb_sum_site(root) \
                    and getattr(node, "step", None) != "final":
                rows = ranges.sum_lane_rows(eval_expr(root.arg, env))
                (one,) = one_lane_sums(
                    [root], [rows], max_groups(node) if node.group_exprs else 1)
                lines.append(
                    f"{ctx.name(node)} {label}: {root!r} "
                    + (f"proven (one int64 lane a page of up to {rows} "
                       f"rows)" if one else "checked (limbs row by row)"))
    return lines
