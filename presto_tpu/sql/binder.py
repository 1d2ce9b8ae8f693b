"""Semantic analysis + logical planning: AST -> plan tree.

Reference analog: the analyzer/planner stack —
``sql/analyzer/StatementAnalyzer.java`` (name/type resolution, scopes),
``sql/planner/LogicalPlanner.java:137`` + ``QueryPlanner``/
``RelationPlanner`` (AST -> PlanNode DAG), and the key optimizer passes
folded in at build time the way AddExchanges folds distribution:

* predicate pushdown (optimizations/PredicatePushDown.java) — WHERE
  conjuncts routed to their source relations before joins;
* cross-join elimination via the equi-join graph
  (optimizations/EliminateCrossJoins.java) — comma-FROM + WHERE becomes
  a join tree greedily, probe side = largest estimated input
  (DetermineJoinDistributionType.java's build-small heuristic);
* partial-aggregation splitting happens in the executor
  (PushPartialAggregationThroughExchange.java analog);
* subquery decorrelation (TransformCorrelatedScalarAggregationToJoin,
  TransformExistsApplyToLateralNode rules): EXISTS -> semi/anti join,
  correlated scalar aggregates -> grouped-agg join, uncorrelated
  scalar subqueries -> single-row cross join.

Scopes are positional: binding produces ``expr.ir`` trees whose
ColumnRefs index the current plan node's output channels.
"""

from __future__ import annotations

import dataclasses
import datetime
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from presto_tpu.catalog import Catalog
from presto_tpu.page import Dictionary
from presto_tpu.expr.ir import AggCall, Call, ColumnRef, Expr, Literal, call, infer_type
from presto_tpu.obs.trace import span
from presto_tpu.planner.plan import (
    AggregationNode,
    Channel,
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
    ValuesNode,
)
from presto_tpu.sql import ast
from presto_tpu.sql.parser import parse_query
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, MICROS_PER_DAY, TIMESTAMP, VARCHAR,
    DecimalType, Type, common_super_type,
)

AGG_FUNCTIONS = {
    "sum", "avg", "count", "min", "max",
    "stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop",
    "bool_and", "bool_or", "every",
    # approx_distinct: real HyperLogLog sketch (m=4096), lowered to a
    # two-level aggregation (see _rewrite_approx_distinct)
    "approx_distinct",
    "min_by", "max_by", "approx_percentile",
    "covar_pop", "covar_samp", "corr", "regr_slope", "regr_intercept",
    "checksum", "arbitrary", "count_if", "geometric_mean",
    "skewness", "kurtosis", "bitwise_and_agg", "bitwise_or_agg",
    "array_agg", "map_agg", "histogram", "map_union",
    # HLL sketches as first-class values (spi HyperLogLogType):
    # approx_set builds one, merge unions them, cardinality estimates
    "approx_set", "merge", "numeric_histogram", "multimap_agg",
    # presto-ml analogs: sufficient-statistic training aggregates
    "learn_regressor", "learn_classifier",
    "learn_libsvm_regressor", "learn_libsvm_classifier",
    # KMV set digests (type/setdigest/BuildSetDigestAggregation.java +
    # MergeSetDigestAggregation.java)
    "make_set_digest", "merge_set_digest",
    # presto-ml classifier evaluation (host-finalized string summary)
    "evaluate_classifier_predictions",
}

# Correlated bindings mark outer-scope columns with this offset so a
# conjunct's inner/outer sides are separable after binding.
_OUTER_BASE = 1 << 20

# Window results bind as sentinel channel refs during select binding and
# are patched to real appended-channel indexes once the aggregation's
# channel count is final.
_WIN_BASE = 1 << 24

WINDOW_FUNCTIONS = {
    "row_number", "rank", "dense_rank", "lead", "lag",
    "first_value", "last_value", "ntile", "percent_rank", "cume_dist",
    "nth_value",
} | AGG_FUNCTIONS

# scalar builtins (reference: operator/scalar/ ~130 files; the engine's
# set grows here + in expr/compile.py)
SCALAR_FUNCTIONS = {
    "abs", "sign", "sqrt", "cbrt", "exp", "ln", "log10", "log2", "power", "pow",
    "sin", "cos", "tan", "asin", "acos", "atan", "atan2",
    "sinh", "cosh", "tanh", "degrees", "radians", "truncate",
    "width_bucket", "is_nan", "is_finite", "is_infinite", "pi", "e",
    "nan", "infinity",
    # bitwise scalars (operator/scalar/BitwiseFunctions.java)
    "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
    "bitwise_shift_left", "bitwise_shift_right", "bit_count",
    # base conversion / binary hashes (VarbinaryFunctions.java)
    "from_base", "to_base", "crc32", "xxhash64", "to_utf8",
    # datetime breadth (DateTimeFunctions.java)
    "date_format", "date_parse", "from_iso8601_date",
    "last_day_of_month", "year_of_week",
    # string breadth (StringFunctions.java)
    "chr", "translate", "normalize", "soundex",
    "levenshtein_distance", "hamming_distance",
    # URL codecs, JSON normalization, binary hash hex forms
    "url_encode", "url_decode", "json_format", "json_parse", "json_size",
    "md5_hex", "sha1_hex", "sha256_hex", "split",
    "ceil", "ceiling", "floor", "round", "mod", "greatest", "least",
    "nullif", "coalesce", "if", "length", "strpos", "upper", "lower",
    "trim", "ltrim", "rtrim", "reverse", "substr",
    "year", "month", "day", "day_of_week", "day_of_year", "quarter", "week",
    "hour", "minute", "second", "millisecond",
    "date_trunc", "date_add", "date_diff", "from_unixtime", "to_unixtime",
    "regexp_like", "regexp_extract", "regexp_replace", "replace",
    "split_part", "lpad", "rpad", "concat", "starts_with", "ends_with",
    "codepoint",
    "json_extract", "json_extract_scalar", "json_array_length", "is_json_scalar",
    "url_extract_host", "url_extract_path", "url_extract_protocol",
    "url_extract_query", "url_extract_port",
    # geospatial (presto-geospatial GeoFunctions.java)
    "st_geometryfromtext", "st_point", "st_distance", "st_contains",
    "st_area", "st_x", "st_y",
    # ML inference (presto-ml regress/classify over array models)
    "regress", "classify", "features",
    # teradata compat (presto-teradata-functions)
    "index", "char2hexint", "nvl",
    # ARRAY / MAP (operator/scalar/ArrayFunctions, MapKeys, MapValues...)
    "cardinality", "contains", "element_at", "array_position",
    "jaccard_index", "intersection_cardinality", "hash_counts",
    "array_min", "array_max", "array_sum", "array_average",
    "array_sort", "array_distinct", "map_keys", "map_values", "map",
    "sequence", "slice", "repeat",
    # ARRAY set algebra + map concat (ArrayIntersectFunction,
    # ArrayUnionFunction, ArrayExceptFunction, ArraysOverlapFunction,
    # ArrayRemoveFunction, MapConcatFunction)
    "array_intersect", "array_union", "array_except", "arrays_overlap",
    "array_remove", "map_concat",
}


class BindError(Exception):
    """User-facing semantic error (SemanticException analog).  ``pos``
    is the character offset into the statement text when the failing
    AST node carried one (parser NodeLocation analog); the statement
    boundary (:meth:`Binder.plan`) renders it as ``line:col``."""

    def __init__(self, message, pos: Optional[int] = None):
        super().__init__(message)
        self.pos = pos


def annotate_position(e: BindError, sql: str) -> BindError:
    """Render a BindError's statement offset as ``line:col`` against
    the statement text (the reference's SemanticException carries a
    NodeLocation the same way).  No-op when no position is known or the
    error was already annotated (structured flag, not a message-text
    sniff — user identifiers may legitimately contain ' at line ')."""
    pos = getattr(e, "pos", None)
    if pos is None or getattr(e, "_annotated", False):
        return e
    line = sql.count("\n", 0, pos) + 1
    col = pos - (sql.rfind("\n", 0, pos) + 1) + 1
    out = BindError(f"{e} at line {line}:{col}", pos=pos)
    out._annotated = True
    return out


@dataclasses.dataclass
class ScopeCol:
    qualifier: Optional[str]
    name: str
    channel: Channel


class Scope:
    """Positional name resolution, optionally chained to an outer
    query's scope (StatementAnalyzer's Scope.java analog).  A parent
    hit resolves to ``len(self) + parent_index`` — the combined index
    space a correlated binding uses to separate inner from outer refs."""

    def __init__(self, cols: Sequence[ScopeCol], parent: Optional["Scope"] = None):
        self.cols = list(cols)
        self.parent = parent

    @classmethod
    def of(cls, node: PlanNode, qualifier: Optional[str] = None) -> "Scope":
        return cls([ScopeCol(qualifier, c.name, c) for c in node.channels])

    def concat(self, other: "Scope") -> "Scope":
        return Scope(self.cols + other.cols)

    def col(self, idx: int) -> ScopeCol:
        if idx < len(self.cols):
            return self.cols[idx]
        return self.parent.col(idx - len(self.cols))

    def resolve(self, qualifier: Optional[str], name: str) -> int:
        hits = [
            i
            for i, c in enumerate(self.cols)
            if c.name == name and (qualifier is None or c.qualifier == qualifier)
        ]
        if not hits:
            if self.parent is not None:
                return len(self.cols) + self.parent.resolve(qualifier, name)
            raise BindError(f"column not found: {qualifier + '.' if qualifier else ''}{name}")
        if len(hits) > 1:
            raise BindError(f"ambiguous column: {name}")
        return hits[0]

    def __len__(self):
        return len(self.cols)


def desugar_quantified(e: ast.Node) -> ast.Node:
    """value op ANY|ALL (subquery) -> existing subquery forms
    (iterative/rule/TransformQuantifiedComparisonApplyToLateralJoin's
    role, done as an AST rewrite):
      = ANY  -> IN        <> ALL -> NOT IN        <> ANY -> NOT (= ALL)
      other op ANY/ALL -> CASE over min/max(S), count(*), count(S) with
      the ANSI edge semantics (the reference QuantifiedComparison
      rewriter's count-based expansion): ALL over empty is TRUE, ANY
      over empty is FALSE, and a non-definitive comparison against a
      set holding NULLs is UNKNOWN."""
    if isinstance(e, ast.Unary) and e.op == "not":
        # NOT (v op ALL/ANY ...) must still desugar underneath
        inner = desugar_quantified(e.operand)
        return e if inner is e.operand else ast.Unary("not", inner)
    if not isinstance(e, ast.QuantifiedComparison):
        return e
    if e.quantifier == "any" and e.op == "=":
        return ast.InSubquery(e.value, e.query, negated=False)
    if e.quantifier == "all" and e.op == "<>":
        return ast.InSubquery(e.value, e.query, negated=True)
    if e.quantifier == "any" and e.op == "<>":
        # v <> ANY S == NOT (v = ALL S) — exact under three-valued logic
        return ast.Unary("not", desugar_quantified(
            dataclasses.replace(e, op="=", quantifier="all")))

    if len(e.query.select) != 1 or isinstance(e.query.select[0].expr,
                                              ast.Star):
        raise BindError("quantified subquery must select one column")

    def scalar(fc: ast.FuncCall) -> ast.ScalarSubquery:
        q = e.query
        # the subquery stays INTACT as a derived table (its ORDER BY /
        # LIMIT apply before the aggregation); only the output column
        # gains a referenceable alias.  Every call builds a FRESH node
        # — subquery planning is keyed by object identity, so shared
        # nodes would double-plan.  KNOWN COST: the CASE forms below
        # re-plan the subquery once per aggregate reference (4-6x); a
        # single derived aggregation computing min/max/count(*)/count
        # together would be 1x (needs multi-column scalar subqueries —
        # future work, quantified comparisons are a rare operator).
        inner = dataclasses.replace(q.select[0], alias="__qc")
        wrapped = ast.Query(
            select=(ast.SelectItem(fc, None),),
            from_=(ast.SubqueryRel(
                dataclasses.replace(q, select=(inner,)), alias="__q"),),
        )
        return ast.ScalarSubquery(wrapped)

    def agg(fn: str) -> ast.FuncCall:
        return ast.FuncCall(fn, (ast.Identifier(("__qc",)),))

    def count_star() -> ast.ScalarSubquery:
        return scalar(ast.FuncCall("count", (), star=True))

    minmax = {("<", "any"): "max", ("<=", "any"): "max",
              (">", "any"): "min", (">=", "any"): "min",
              ("<", "all"): "min", ("<=", "all"): "min",
              (">", "all"): "max", (">=", "all"): "max"}
    key = (e.op, e.quantifier)
    if key in minmax:
        cmp = ast.Binary(e.op, e.value, scalar(agg(minmax[key])))
    elif e.op == "=" and e.quantifier == "all":
        cmp = ast.Binary("and",
                         ast.Binary("=", e.value, scalar(agg("min"))),
                         ast.Binary("=", e.value, scalar(agg("max"))))
    else:
        raise BindError(f"{e.op} {e.quantifier.upper()} (subquery) unsupported")

    true_, false_ = ast.NumberLit("1"), ast.NumberLit("0")
    no_nulls = ast.Binary("=", count_star(), scalar(agg("count")))
    empty = ast.Binary("=", count_star(), ast.NumberLit("0"))
    if e.quantifier == "all":
        whens = (
            (empty, true_),                              # vacuous truth
            (ast.Binary("and", cmp, no_nulls), true_),
            (cmp, ast.NullLit()),       # non-nulls passed, NULLs unknown
            (ast.Unary("not", cmp), false_),             # definite miss
        )
    else:  # any
        whens = (
            (empty, false_),
            (cmp, true_),               # some non-null element satisfies
            (ast.Binary("and", ast.Unary("not", cmp), no_nulls), false_),
        )
    # `CASE ... END = 1` keeps the three-valued result boolean-typed
    # (TRUE/FALSE literals parse as numbers in this grammar)
    return ast.Binary("=", ast.Case(whens=whens, else_=ast.NullLit()),
                      ast.NumberLit("1"))


_INTERVAL_MICROS = {"second": 1_000_000, "minute": 60_000_000,
                    "hour": 3_600_000_000, "day": 86_400_000_000}


def _interval_literal(iv: "ast.IntervalLit"):
    """(type, device value) of an interval literal — micros for the
    day-second family, months for year-month.  Accepts fractional
    seconds ('1.5' SECOND) and the 'Y-M' year-to-month form
    (sql/tree/IntervalLiteral.java + DateTimeUtils.parse*Interval)."""
    from presto_tpu.types import INTERVAL_DAY_SECOND, INTERVAL_YEAR_MONTH

    sign = -1 if iv.negative else 1
    text = iv.value.strip()
    if text.startswith(("-", "+")):  # sign inside the string
        if text[0] == "-":
            sign = -sign
        text = text[1:].strip()
    try:
        if iv.unit in _INTERVAL_MICROS:
            if "." in text:
                n = round(float(text) * _INTERVAL_MICROS[iv.unit])
            else:
                n = int(text) * _INTERVAL_MICROS[iv.unit]
            return INTERVAL_DAY_SECOND, sign * n
        if "-" in text and iv.unit == "year":  # 'Y-M' YEAR TO MONTH
            y, m = text.split("-", 1)
            return INTERVAL_YEAR_MONTH, sign * (int(y) * 12 + int(m))
        return (INTERVAL_YEAR_MONTH,
                sign * int(text) * (12 if iv.unit == "year" else 1))
    except ValueError:
        raise BindError(f"malformed interval literal {iv.value!r}")


def split_conjuncts(node: Optional[ast.Node]) -> List[ast.Node]:
    if node is None:
        return []
    if isinstance(node, ast.Binary) and node.op == "and":
        return split_conjuncts(node.left) + split_conjuncts(node.right)
    return [desugar_quantified(node)]


def expr_refs(e: Expr) -> List[int]:
    if isinstance(e, ColumnRef):
        return [e.index]
    if isinstance(e, Call):
        return [r for a in e.args for r in expr_refs(a)]
    from presto_tpu.expr.ir import LambdaExpr

    if isinstance(e, LambdaExpr):
        return expr_refs(e.body)  # captured outer-channel references
    return []


#: Joda-Time pattern letters -> the MySQL codes date_format speaks
#: (format_datetime's date-field subset; runs of the same letter pick
#: padded vs plain forms as Joda does)
_JODA_RUNS = {
    "yyyy": "%Y", "yy": "%y", "y": "%Y", "MMMM": "%M", "MMM": "%b",
    "MM": "%m", "M": "%c", "dd": "%d", "d": "%e", "EEEE": "%W",
    "EEE": "%a", "E": "%a", "DDD": "%j",
    # 'D' (unpadded day-of-year) has no MySQL code -> rejected
}


def _joda_to_mysql(fmt: str) -> str:
    out = []
    i = 0
    while i < len(fmt):
        ch = fmt[i]
        if ch == "'":
            if i + 1 < len(fmt) and fmt[i + 1] == "'":
                out.append("'")  # Joda '' = one literal quote
                i += 2
                continue
            j = i + 1
            lit = []
            while j < len(fmt):
                if fmt[j] == "'":
                    if j + 1 < len(fmt) and fmt[j + 1] == "'":
                        lit.append("'")
                        j += 2
                        continue
                    break
                lit.append(fmt[j])
                j += 1
            else:
                raise BindError("unterminated quote in datetime pattern")
            out.append("".join(lit).replace("%", "%%"))
            i = j + 1
            continue
        if ch.isalpha():
            j = i
            while j < len(fmt) and fmt[j] == ch:
                j += 1
            run = fmt[i:j]
            got = _JODA_RUNS.get(run)
            if got is None:
                raise BindError(
                    f"unsupported datetime pattern letter run '{run}'")
            out.append(got)
            i = j
            continue
        out.append(ch.replace("%", "%%"))
        i += 1
    return "".join(out)


def remap_expr(e: Expr, mapping: Dict[int, int]) -> Expr:
    if isinstance(e, ColumnRef):
        return ColumnRef(type=e.type, index=mapping[e.index], name=e.name)
    if isinstance(e, Call):
        return Call(type=e.type, fn=e.fn, args=tuple(remap_expr(a, mapping) for a in e.args))
    from presto_tpu.expr.ir import LambdaExpr

    if isinstance(e, LambdaExpr):
        return LambdaExpr(type=e.type, params=e.params,
                          body=remap_expr(e.body, mapping))
    return e


def _parse_date(s: str) -> int:
    d = datetime.date.fromisoformat(s)
    return (d - datetime.date(1970, 1, 1)).days


def _parse_timestamp(s: str) -> int:
    """'YYYY-MM-DD[ HH:MM:SS[.ffffff]]' -> epoch microseconds."""
    s = s.strip()
    if " " in s or "T" in s:
        dt = datetime.datetime.fromisoformat(s.replace("T", " "))
    else:
        d = datetime.date.fromisoformat(s)
        dt = datetime.datetime(d.year, d.month, d.day)
    delta = dt - datetime.datetime(1970, 1, 1)
    return (delta.days * 86_400 + delta.seconds) * 1_000_000 + delta.microseconds


def _parse_time_of_day(s: str) -> int:
    """'HH:MM:SS[.ffffff]' -> microseconds since midnight."""
    t = datetime.time.fromisoformat(s.strip())
    return ((t.hour * 3600 + t.minute * 60 + t.second) * 1_000_000
            + t.microsecond)


def _shift_date(days: int, n: int, unit: str) -> int:
    d = datetime.date(1970, 1, 1) + datetime.timedelta(days=days)
    if unit == "day":
        d = d + datetime.timedelta(days=n)
    else:
        months = n * (12 if unit == "year" else 1)
        m = d.month - 1 + months
        y = d.year + m // 12
        m = m % 12 + 1
        day = min(d.day, [31, 29 if y % 4 == 0 and (y % 100 != 0 or y % 400 == 0) else 28,
                          31, 30, 31, 30, 31, 31, 30, 31, 30, 31][m - 1])
        d = datetime.date(y, m, day)
    return (d - datetime.date(1970, 1, 1)).days


def _flatten_bool(e: Expr, fn: str) -> List[Expr]:
    if isinstance(e, Call) and e.fn == fn:
        return _flatten_bool(e.args[0], fn) + _flatten_bool(e.args[1], fn)
    return [e]


def _extract_common_or(ir: Expr) -> List[Expr]:
    """Factor conjuncts common to every OR branch out of the OR
    (ExtractCommonPredicatesExpressionRewriter analog, on bound IR with
    structural equality). Returns the replacement conjunct list."""
    if not (isinstance(ir, Call) and ir.fn == "or"):
        return [ir]
    branches = [_flatten_bool(b, "and") for b in _flatten_bool(ir, "or")]
    common = [c for c in branches[0]
              if all(any(c == d for d in bc) for bc in branches[1:])]
    if not common:
        return [ir]
    reduced = []
    for bc in branches:
        rest = [c for c in bc if not any(c == d for d in common)]
        if not rest:
            return common  # one branch is fully covered: OR is implied
        out = rest[0]
        for c in rest[1:]:
            out = call("and", out, c)
        reduced.append(out)
    new_or = reduced[0]
    for b in reduced[1:]:
        new_or = call("or", new_or, b)
    return common + [new_or]


def _flatten_bool_ast(e: ast.Node, op: str) -> List[ast.Node]:
    if isinstance(e, ast.Binary) and e.op == op:
        return _flatten_bool_ast(e.left, op) + _flatten_bool_ast(e.right, op)
    return [e]


def _extract_common_or_ast(c: ast.Node) -> List[ast.Node]:
    """_extract_common_or at the AST level (frozen dataclasses compare
    structurally): (X and A) or (X and B) -> [X, (A or B)].  Lets a
    correlation conjunct shared by every OR branch factor out so
    _split_correlation can classify it (the TPC-DS q41/q85 shape)."""
    if not (isinstance(c, ast.Binary) and c.op == "or"):
        return [c]
    branches = [_flatten_bool_ast(b, "and") for b in _flatten_bool_ast(c, "or")]
    common = [x for x in branches[0] if all(x in bc for bc in branches[1:])]
    if not common:
        return [c]
    reduced = []
    for bc in branches:
        rest = [x for x in bc if x not in common]
        if not rest:
            return common  # one branch fully covered: OR is implied
        out = rest[0]
        for x in rest[1:]:
            out = ast.Binary("and", out, x)
        reduced.append(out)
    new_or = reduced[0]
    for b in reduced[1:]:
        new_or = ast.Binary("or", new_or, b)
    return common + [new_or]


def _iter_child_nodes(v):
    """Yield ast.Node values inside a field value, flattening nested
    tuples (Case.whens is a tuple of (cond, result) pairs)."""
    if isinstance(v, ast.Node):
        yield v
    elif isinstance(v, tuple):
        for x in v:
            yield from _iter_child_nodes(x)


def _find_mark_subqueries(e: ast.Node, out: List[ast.Node]) -> None:
    """Collect Exists/InSubquery nodes inside a general boolean
    expression (not descending into their query bodies) — the operands
    the mark-join lowering replaces with boolean columns."""
    if isinstance(e, (ast.Exists, ast.InSubquery)):
        out.append(e)
        return
    if isinstance(e, (ast.Query, ast.Union, ast.ScalarSubquery)):
        return
    if dataclasses.is_dataclass(e):
        for f in dataclasses.fields(e):
            for x in _iter_child_nodes(getattr(e, f.name)):
                _find_mark_subqueries(x, out)


def _find_scalar_subqueries(e: ast.Node, out: List[ast.Node]) -> None:
    """Collect ScalarSubquery nodes inside an expression (not descending
    into their query bodies)."""
    if isinstance(e, ast.ScalarSubquery):
        out.append(e)
        return
    if isinstance(e, (ast.Query, ast.Union, ast.InSubquery, ast.Exists)):
        return
    if dataclasses.is_dataclass(e):
        for f in dataclasses.fields(e):
            for x in _iter_child_nodes(getattr(e, f.name)):
                _find_scalar_subqueries(x, out)


def _is_subquery_conjunct(c: ast.Node) -> bool:
    if isinstance(c, (ast.InSubquery, ast.Exists)):
        return True
    if isinstance(c, ast.Unary) and c.op == "not":
        return _is_subquery_conjunct(c.operand)
    if isinstance(c, ast.Binary) and c.op in ("=", "<>", "<", "<=", ">", ">="):
        subs: List[ast.Node] = []
        _find_scalar_subqueries(c, subs)
        if subs:
            return True
    # EXISTS/IN-subquery anywhere inside (OR of EXISTS etc.): the
    # mark-join fallback owns these
    marks: List[ast.Node] = []
    _find_mark_subqueries(c, marks)
    return bool(marks)


@dataclasses.dataclass
class AggCtx:
    """Aggregation binding context: group expr matching + agg collection."""

    group_asts: List[ast.Node]
    group_irs: List[Expr]  # over the pre-agg scope
    aggs: List[AggCall] = dataclasses.field(default_factory=list)
    # grouping-set membership masks (GroupIdNode's set_masks) when the
    # aggregation came from GROUPING SETS/ROLLUP/CUBE — powers grouping()
    set_masks: Optional[List[List[bool]]] = None

    def key_ref(self, i: int) -> ColumnRef:
        return ColumnRef(type=self.group_irs[i].type, index=i)

    def agg_ref(self, agg: AggCall) -> ColumnRef:
        from presto_tpu.ops.aggregate import output_type

        for j, a in enumerate(self.aggs):
            if a == agg:
                return ColumnRef(type=output_type(a), index=len(self.group_irs) + j)
        self.aggs.append(agg)
        return ColumnRef(type=output_type(agg), index=len(self.group_irs) + len(self.aggs) - 1)


@dataclasses.dataclass
class Term:
    """One FROM relation: its plan + scope + global channel offset."""

    node: PlanNode
    scope: Scope
    offset: int = 0


class Binder:
    """Plans one SELECT query against a catalog."""

    def __init__(self, catalog: Catalog, session=None):
        self.catalog = catalog
        self.session = session
        # subquery conjuncts discovered while joining the current
        # query's FROM terms, applied after the join tree is built
        self._pending_subqueries: List[Tuple[ast.Node, Scope]] = []
        # window expressions registered while binding the current
        # query's select/order items: ast -> (slot, spec, WindowFunc)
        self._windows: List[Tuple[ast.WindowExpr, object, List[Expr], List[Expr], List[bool]]] = []
        self._win_slots: Dict[ast.WindowExpr, int] = {}
        # planned scalar-subquery marker refs keyed by id(ast node),
        # live only while binding the enclosing conjunct
        self._scalar_refs: Dict[int, ColumnRef] = {}
        # Exists/InSubquery -> mark-join boolean ref (EXISTS under OR)
        self._mark_refs: Dict[int, ColumnRef] = {}
        # UNNEST relations of the FROM clause currently being flattened
        self._from_unnests: List[ast.Unnest] = []
        # in-scope CTE definitions (WITH name AS (...)): name -> query ast
        self._ctes: Dict[str, ast.Node] = {}
        # views currently being expanded (cycle detection, the
        # reference's StatementAnalyzer.analyzeView recursion guard)
        self._view_stack: List[tuple] = []
        # the statement's single now() instant (reset per plan_ast)
        self._now: Optional[float] = None
        # lambda parameter scopes (innermost last): name -> LambdaVar
        self._lambda_params: List[Dict[str, object]] = []
        # statement-unique LambdaVar slots: shadowing-safe nesting
        self._lambda_slot_seq = iter(range(1 << 30))
        # CBO stats (cost/StatsCalculator.java analog); memo is safe to
        # share across plan() calls since plan nodes are identity-keyed
        from presto_tpu.planner.stats import StatsCalculator

        self._stats = StatsCalculator()

    def session_user(self) -> str:
        return self.session.user if self.session is not None else "presto"

    def _row_field(self, base, field: str):
        """expr.field over a ROW value -> the field's column slice
        (DereferenceExpression row access)."""
        t = base.type
        if t.name != "row":
            raise BindError(f"field access on non-row type {t}")
        if not t.field_names:
            raise BindError("row has no named fields (CAST to "
                            "ROW(name type, ...) to name them)")
        fl = field.lower()
        names = [n.lower() for n in t.field_names]
        if fl not in names:
            raise BindError(f"row has no field {field!r}")
        i = names.index(fl)
        # ops/container.row_field is 1-based (SQL subscript convention)
        return Call(type=t.fields[i], fn="row_field",
                    args=(base, Literal(type=BIGINT, value=i + 1)))

    # ==================================================================
    def _query_now(self) -> float:
        """One wall-clock instant per planned query: every
        current_date/current_timestamp/now() in a statement sees the
        same time (Session.getStartTime in the reference)."""
        if self._now is None:
            import time as _time

            self._now = _time.time()
        return self._now

    def plan(self, sql: str) -> OutputNode:
        self._stats.reset()  # don't pin prior queries' plan trees
        try:
            return self.plan_ast(parse_query(sql))
        except BindError as e:
            annotated = annotate_position(e, sql)
            if annotated is not e:
                # keep the internal traceback plan_ast's SPI wrap
                # promised (__cause__), don't suppress it
                raise annotated from e.__cause__
            raise

    def plan_ast(self, q: ast.Node,
                 validate_rewrites: Optional[bool] = None) -> OutputNode:
        self._now = None  # fresh instant for this statement
        # feedback loop: under the `feedback_stats` session property the
        # stats calculator consults the plan-history store (observed
        # actuals from prior executions override textbook selectivities
        # on structural-signature match).  Resolved per statement — the
        # session can toggle it between queries on this binder.
        self._stats.history = None
        if self.session is not None and bool(
                self.session.get("feedback_stats")):
            from presto_tpu.obs.history import (
                HistoricalStatsProvider, default_history,
            )

            store = default_history()
            if store is not None:
                self._stats.history = HistoricalStatsProvider(store)
        try:
            from presto_tpu import analysis

            if validate_rewrites is None:
                validate_rewrites = analysis.rewrite_validation_enabled() or (
                    self.session is not None
                    and bool(self.session.get("validate_rewrites")))
            # table resolution and its statistics (the connectors' row
            # counts: obs.reads.host_read "row_count") are bind's
            with span("plan:bind", cat="lifecycle"):
                node, names = self._plan_query_like(q)
            out = OutputNode(node, names)
            with span("plan:check", cat="lifecycle"):
                if analysis.validation_enabled() or (
                        self.session is not None
                        and bool(self.session.get("validate_plans"))):
                    # pre-optimization half of the validate_plans
                    # contract: a clean bound plan isolates any later
                    # violation to a rewrite (the runner validates the
                    # optimized plan)
                    analysis.assert_valid(out)
                if analysis.kernel_validation_enabled() or (
                        self.session is not None
                        and bool(self.session.get("validate_kernels"))):
                    # same pre-optimization split for the
                    # kernel-soundness tier: a clean bound plan pins any
                    # post-optimization hazard on the rewrite that
                    # introduced it
                    analysis.assert_kernel_sound(out)
            # iterative rule engine over the bound plan
            # (sql/planner/iterative/IterativeOptimizer.java)
            from presto_tpu.planner.iterative import IterativeOptimizer

            with span("plan:optimize", cat="lifecycle"):
                opt = IterativeOptimizer(validate=validate_rewrites)
                out = opt.optimize(out)
            out._optimizer_report = opt.stats
            with span("plan:estimates", cat="lifecycle"):
                self._enable_index_joins(out)
                # estimate capture: stamp the FINAL plan with its
                # bind-time row estimates under the structural stats
                # keys, so EXPLAIN ANALYZE can print est-vs-actual per
                # operator and the history store can attribute
                # misestimates (planner/stats.capture_estimates;
                # feedback applied above via _stats.history)
                from presto_tpu.planner.stats import capture_estimates

                out._estimates = capture_estimates(out, self._stats)
            return out
        except (BindError, SyntaxError):
            raise
        except (KeyError, IndexError, AssertionError, TypeError) as e:
            # SPI boundary: internal exceptions must not leak raw to the
            # user (the r5 ``KeyError: frozenset()`` class).  The
            # message carries through verbatim; the original traceback
            # rides __cause__ for debugging.
            msg = (e.args[0] if e.args and isinstance(e.args[0], str)
                   else (str(e) or type(e).__name__))
            raise BindError(msg) from e

    def _enable_index_joins(self, root: PlanNode) -> None:
        """Flag (or side-swap) joins where one side is a bare scan of an
        index-capable connector and the other is much smaller: fetching
        build rows by probe keys beats the full scan
        (IndexJoinOptimizer.java).  The hash planner puts the largest
        term on the probe side, so the indexed scan usually arrives as
        ``left`` — inner joins swap sides behind a reordering
        projection."""
        from presto_tpu.planner.iterative import _replace_sources

        def indexable(scan: PlanNode, keys) -> bool:
            if not (isinstance(scan, TableScanNode) and not scan.constraints):
                return False
            if not all(isinstance(k, ColumnRef)
                       and k.type.name in ("bigint", "integer") for k in keys):
                return False
            conn = self.catalog.connector(scan.handle.connector_name)
            if not (hasattr(conn, "supports_index")
                    and hasattr(conn, "index_lookup")):
                return False
            key_cols = [scan.handle.columns[scan.columns[k.index]].name
                        for k in keys]
            return conn.supports_index(scan.handle.table, key_cols)

        def walk(n: PlanNode) -> PlanNode:
            srcs = n.sources
            if srcs:
                new = [walk(s) for s in srcs]
                if any(a is not b for a, b in zip(new, srcs)):
                    _replace_sources(n, new)
            if not isinstance(n, JoinNode):
                return n
            if (n.kind in ("inner", "semi", "anti")
                    and indexable(n.right, n.right_keys)
                    and self._estimate(n.left) * 10 < self._estimate(n.right)):
                n.use_index = True
                return n
            if (n.kind == "inner" and not n.use_index
                    and indexable(n.left, n.left_keys)
                    and self._estimate(n.right) * 10 < self._estimate(n.left)):
                nl, nr = len(n.left.channels), len(n.right.channels)
                swapped = JoinNode(
                    left=n.right, right=n.left,
                    left_keys=list(n.right_keys), right_keys=list(n.left_keys),
                    kind="inner", use_index=True,
                )
                chans = swapped.channels  # right-side first
                projections = (
                    [ColumnRef(type=chans[nr + i].type, index=nr + i)
                     for i in range(nl)]
                    + [ColumnRef(type=chans[i].type, index=i) for i in range(nr)]
                )
                names = ([c.name for c in chans[nr:]]
                         + [c.name for c in chans[:nr]])
                return ProjectNode(swapped, projections, names)
            return n

        walk(root)

    def _plan_query_like(self, q: ast.Node) -> Tuple[PlanNode, List[str]]:
        if isinstance(q, ast.With):
            # CTEs expand by name substitution: TableRef resolution
            # consults the scoped registry first (sql/tree/With.java)
            saved = dict(self._ctes)
            try:
                for name, sub in q.ctes:
                    self._ctes[name.lower()] = sub
                return self._plan_query_like(q.body)
            finally:
                self._ctes = saved
        if isinstance(q, ast.Union):
            return self._plan_union(q)
        if isinstance(q, ast.SetOp):
            return self._plan_setop(q)
        return self._plan_query(q)

    def _plan_setop(self, q: ast.SetOp) -> Tuple[PlanNode, List[str]]:
        """INTERSECT -> distinct(left) SEMI-joined to right on every
        column; EXCEPT -> ANTI join (the reference lowers through
        SetOperationNodeTranslator to the same semi/anti shapes).
        NULLs compare equal, per set-operation semantics — the join
        key packing already treats NULL keys as one class."""
        label = q.kind.upper()
        lnode, rnode, lnames = self._plan_set_arms(q, label)
        distinct_left = AggregationNode(
            lnode,
            [ColumnRef(type=c.type, index=i) for i, c in enumerate(lnode.channels)],
            lnames, [], [],
            max_groups=self._distinct_capacity(lnode),
        )
        join = JoinNode(
            left=distinct_left, right=rnode,
            left_keys=[ColumnRef(type=c.type, index=i)
                       for i, c in enumerate(distinct_left.channels)],
            right_keys=[ColumnRef(type=c.type, index=i)
                        for i, c in enumerate(rnode.channels)],
            kind="semi" if q.kind == "intersect" else "anti",
            null_safe_keys=True,  # set-op rows compare IS NOT DISTINCT FROM
        )
        names = lnames
        node = self._wrap_order_limit(join, names, q.order_by, q.limit, label)
        return node, names

    def _plan_set_arms(self, q, label: str):
        """Shared arm planning for UNION/INTERSECT/EXCEPT: plan both
        sides, check arity, align types via cast projections."""
        lnode, lnames = self._plan_query_like(q.left)
        rnode, rnames = self._plan_query_like(q.right)
        if len(lnode.channels) != len(rnode.channels):
            raise BindError(f"{label} arms have different column counts")
        targets = [
            common_super_type(a.type, b.type)
            for a, b in zip(lnode.channels, rnode.channels)
        ]
        lnode = self._coerce_columns(lnode, targets, lnames)
        rnode = self._coerce_columns(rnode, targets, lnames)
        return lnode, rnode, lnames

    def _wrap_order_limit(self, node: PlanNode, names: List[str], order_by,
                          limit, label: str) -> PlanNode:
        """Set-operation-level ORDER BY (names/ordinals) + LIMIT."""
        order_channels: List[ColumnRef] = []
        for o in order_by:
            e = o.expr
            if isinstance(e, ast.NumberLit):
                i = int(e.text) - 1
            elif isinstance(e, ast.Identifier) and e.name in names:
                i = names.index(e.name)
            else:
                raise BindError(
                    f"{label} ORDER BY must use output names or ordinals")
            order_channels.append(ColumnRef(type=node.channels[i].type, index=i))
        if order_by:
            asc = [o.ascending for o in order_by]
            nf = [o.nulls_first if o.nulls_first is not None else (not o.ascending)
                  for o in order_by]
            if limit is not None:
                return TopNNode(node, order_channels, asc, limit, nf)
            return SortNode(node, order_channels, asc, nf)
        if limit is not None:
            return LimitNode(node, limit)
        return node

    def _plan_union(self, u: ast.Union) -> Tuple[PlanNode, List[str]]:
        from presto_tpu.planner.plan import UnionNode

        lnode, rnode, names = self._plan_set_arms(u, "UNION")
        node: PlanNode = UnionNode([lnode, rnode])
        if u.distinct:
            node = AggregationNode(
                node,
                [ColumnRef(type=c.type, index=i) for i, c in enumerate(node.channels)],
                names, [], [],
                max_groups=self._distinct_capacity(node),
            )
        node = self._wrap_order_limit(node, names, u.order_by, u.limit, "UNION")
        return node, names

    def _coerce_columns(self, node: PlanNode, targets: List[Type], names: List[str]) -> PlanNode:
        if all(c.type == t for c, t in zip(node.channels, targets)):
            return node
        projections = []
        for i, (c, t) in enumerate(zip(node.channels, targets)):
            ref = ColumnRef(type=c.type, index=i)
            if c.type == t:
                projections.append(ref)
            elif t.name == "double":
                projections.append(call("cast_double", ref))
            elif t.is_decimal:
                # rescale through exact decimal addition of 0
                projections.append(call("add", ref, Literal(type=t, value=0)))
            elif t.name == "bigint":
                projections.append(call("cast_bigint", ref))
            else:
                raise BindError(f"cannot unify UNION column types {c.type} and {t}")
        return ProjectNode(node, projections, list(names))

    # ==================================================================
    # relation planning
    # ==================================================================
    def _plan_relation(self, rel: ast.Node) -> Tuple[PlanNode, Scope]:
        if isinstance(rel, ast.TableRef):
            cte = self._ctes.get(rel.name.lower())
            if cte is not None:
                node, names = self._plan_query_like(cte)
                qual = rel.alias or rel.name
                scope = Scope(
                    [ScopeCol(qual, n, c) for n, c in zip(names, node.channels)]
                )
                return node, scope
            view = self.catalog.lookup_view(rel.name, self.session) \
                if hasattr(self.catalog, "lookup_view") else None
            if view is not None:
                # view expansion: re-parse and re-bind the stored SQL
                # under the view's own creation-time namespace
                # (StatementAnalyzer.java:789 via metadata.getView)
                key, vdef = view
                if key in self._view_stack:
                    raise BindError(
                        "view is recursive: " + ".".join(key))
                if getattr(rel, "sample", None) is not None:
                    # the sample clause rides TableScanNode; silently
                    # scanning 100% of an expanded view would be a
                    # wrong result, so reject loudly
                    raise BindError("TABLESAMPLE over a view is not supported")
                from presto_tpu.sql.parser import parse_query

                saved = None
                if self.session is not None:
                    saved = (self.session.catalog, self.session.schema)
                    self.session.catalog = vdef.catalog
                    self.session.schema = vdef.schema
                self._view_stack.append(key)
                saved_ctes, self._ctes = self._ctes, {}
                try:
                    node, names = self._plan_query_like(parse_query(vdef.sql))
                finally:
                    self._view_stack.pop()
                    self._ctes = saved_ctes
                    if saved is not None:
                        self.session.catalog, self.session.schema = saved
                qual = rel.alias or rel.name.split(".")[-1]
                scope = Scope(
                    [ScopeCol(qual, n, c) for n, c in zip(names, node.channels)]
                )
                return node, scope
            handle = self.catalog.resolve(rel.name, session=self.session)
            scan = TableScanNode(handle, list(range(len(handle.columns))),
                                 sample=getattr(rel, "sample", None))
            # a catalog-qualified name aliases to its bare table name
            return scan, Scope.of(scan, rel.alias or rel.name.split(".")[-1])
        if isinstance(rel, ast.ValuesRel):
            return self._plan_values(rel)
        if isinstance(rel, ast.SubqueryRel):
            node, names = self._plan_query_like(rel.query)
            scope = Scope(
                [ScopeCol(rel.alias, n, c) for n, c in zip(names, node.channels)]
            )
            return node, scope
        if isinstance(rel, ast.JoinRel):
            return self._plan_join_rel(rel)
        raise BindError(f"unsupported relation {rel!r}")

    def _flatten_from(self, rels: Sequence[ast.Node]) -> Tuple[List[Term], List[ast.Node]]:
        """Flatten comma relations + inner join trees into terms and a
        conjunct pool (EliminateCrossJoins flattening)."""
        terms: List[Term] = []
        conjuncts: List[ast.Node] = []

        def walk(rel: ast.Node):
            if isinstance(rel, ast.JoinRel) and rel.kind == "inner":
                walk(rel.left)
                walk(rel.right)
                conjuncts.extend(split_conjuncts(rel.on))
            elif isinstance(rel, ast.JoinRel) and rel.kind == "cross":
                walk(rel.left)
                walk(rel.right)
            elif isinstance(rel, ast.Unnest):
                # lateral: binds against the joined FROM scope, applied
                # after the join graph (UNNEST is always a cross-join
                # expansion of the preceding terms)
                self._from_unnests.append(rel)
            else:
                node, scope = self._plan_relation(rel)
                terms.append(Term(node, scope))

        for r in rels:
            walk(r)
        off = 0
        for t in terms:
            t.offset = off
            off += len(t.scope)
        return terms, conjuncts

    def _input_presorted(self, node: PlanNode, group_irs) -> bool:
        """True when the aggregation input provably arrives with equal
        group keys contiguous: the input chain is scan(+filter/projection
        pass-through) of a table whose declared sort order's prefix is
        exactly the group-key set (connector ``sort_order`` metadata —
        the reference's ConnectorMetadata local properties feeding
        StreamingAggregationOperator selection)."""
        remap: Optional[Dict[int, int]] = None  # None = identity (no Project seen)
        cur = node
        while True:
            if isinstance(cur, FilterNode):
                cur = cur.source
            elif isinstance(cur, ProjectNode):
                proj_map = {}
                for i, p in enumerate(cur.projections):
                    if isinstance(p, ColumnRef):
                        proj_map[i] = p.index
                src_items = (remap.items() if remap is not None else
                             ((i, i) for i in range(len(cur.channels))))
                remap = {}
                for out_i, in_i in src_items:
                    if in_i in proj_map:
                        remap[out_i] = proj_map[in_i]
                cur = cur.source
            else:
                break
        if not isinstance(cur, TableScanNode):
            return False
        conn = self.catalog.connector(cur.handle.connector_name)
        so = conn.sort_order(cur.handle.table) if hasattr(conn, "sort_order") else None
        if not so:
            return False
        names = set()
        for e in group_irs:
            if not isinstance(e, ColumnRef):
                return False
            idx = e.index if remap is None else remap.get(e.index)
            if idx is None or idx >= len(cur.columns):
                return False
            names.add(cur.handle.columns[cur.columns[idx]].name)
        k = len(names)
        return 0 < k <= len(so) and set(so[:k]) == names

    def _plan_values(self, rel: ast.ValuesRel) -> Tuple[PlanNode, Scope]:
        """VALUES rows -> ValuesNode (sql/tree/Values.java): literal
        cells bind standalone; column types are the per-position common
        supertypes with NULL literals adopting them."""
        empty = Scope([])
        bound = [[self._bind(c, empty) for c in row] for row in rel.rows]
        if not bound:
            raise BindError("empty VALUES")
        arity = len(bound[0])
        for row in bound:
            if len(row) != arity:
                raise BindError("VALUES rows differ in arity")
            for j, cell in enumerate(row):
                if isinstance(cell, Call) and cell.fn == "array_construct" \
                        and all(isinstance(a, Literal) for a in cell.args):
                    # constant-fold ARRAY[...] literals to list values
                    vals = []
                    for a in cell.args:
                        if a.value is None:
                            vals.append(None)
                        elif a.type.is_decimal:
                            # plain python value; the page encoder
                            # re-scales to the element type
                            vals.append(a.value / 10 ** (a.type.scale or 0))
                        else:
                            vals.append(a.value)
                    row[j] = Literal(type=cell.type, value=vals)
                elif not isinstance(cell, Literal):
                    raise BindError("VALUES cells must be literals")
        types: List[Type] = []
        for j in range(arity):
            t = None
            for row in bound:
                cell = row[j]
                if cell.value is None:
                    continue
                if t is None:
                    t = cell.type
                elif t.is_array and cell.type.is_array:
                    from presto_tpu.types import ArrayType

                    t = ArrayType(
                        common_super_type(t.element, cell.type.element),
                        max(t.max_elems, cell.type.max_elems))
                else:
                    t = common_super_type(t, cell.type)
            types.append(t if t is not None else BIGINT)
        names = (list(rel.column_names) if rel.column_names
                 else [f"_col{j}" for j in range(arity)])
        if len(names) != arity:
            raise BindError("VALUES alias declares wrong column count")

        # string columns dictionary-encode over their distinct values
        dictionaries: List = []
        for j, t in enumerate(types):
            if t.is_string:
                values = sorted({row[j].value for row in bound
                                 if row[j].value is not None})
                dictionaries.append(Dictionary(values))
            else:
                dictionaries.append(None)

        def cell_value(cell: Literal, t: Type, d):
            if cell.value is None:
                return None
            v = cell.value
            if d is not None:
                return d.code_of(str(v))
            if t.is_decimal and cell.type.is_decimal:
                return v * 10 ** ((t.scale or 0) - (cell.type.scale or 0))
            if t.name == "double" and cell.type.is_decimal:
                return v / 10 ** (cell.type.scale or 0)
            return v

        rows = [
            tuple(cell_value(c, t, d)
                  for c, t, d in zip(row, types, dictionaries))
            for row in bound
        ]
        node = ValuesNode(names=names, types=types, rows=rows,
                          dictionaries=dictionaries)
        return node, Scope.of(node, rel.alias)

    def _names_resolvable(self, e: ast.Node, scope: Scope) -> bool:
        """True if every free Identifier in ``e`` resolves in ``scope``
        (subquery bodies are skipped — they bind their own scopes)."""
        ok = True

        def walk(n):
            nonlocal ok
            if not ok or not isinstance(n, ast.Node):
                return
            if isinstance(n, ast.InSubquery):
                walk(n.value)  # the probe value is free; the body is not
                return
            if isinstance(n, (ast.ScalarSubquery, ast.Exists)):
                return  # inner scopes resolve separately
            if isinstance(n, ast.Identifier):
                qualifier = n.parts[0] if len(n.parts) > 1 else None
                try:
                    scope.resolve(qualifier, n.parts[-1])
                except BindError:
                    ok = False
                return
            for f in dataclasses.fields(n):
                visit(getattr(n, f.name))

        def visit(v):
            # tuples nest (Case.whens is a tuple of (cond, result) pairs)
            if isinstance(v, tuple):
                for x in v:
                    visit(x)
            else:
                walk(v)

        walk(e)
        return ok

    def _apply_unnest(self, node: PlanNode, scope: Scope,
                      un: ast.Unnest) -> Tuple[PlanNode, Scope]:
        """UNNEST(args) lateral expansion (UnnestOperator.java:35)."""
        from presto_tpu.planner.plan import UnnestNode

        exprs = [self._bind(a, scope) for a in un.args]
        ncols = 0
        for e in exprs:
            if not (e.type.is_array or e.type.is_map):
                raise BindError(f"UNNEST argument must be ARRAY or MAP, got {e.type}")
            ncols += 2 if e.type.is_map else 1
        want = ncols + (1 if un.ordinality else 0)
        if un.column_names:
            if len(un.column_names) != want:
                raise BindError(
                    f"UNNEST alias declares {len(un.column_names)} columns, "
                    f"expansion produces {want}")
            names = list(un.column_names)
        else:
            names = [f"col{i+1}" for i in range(ncols)]
            if un.ordinality:
                names.append("ordinality")
        out = UnnestNode(node, exprs, names, un.ordinality)
        new_cols = [
            ScopeCol(un.alias, c.name, c) for c in out.channels[len(scope):]
        ]
        return out, Scope(scope.cols + new_cols)

    def _plan_join_rel(self, rel: ast.JoinRel) -> Tuple[PlanNode, Scope]:
        """Explicit JOIN trees. Inner joins route through the join-graph
        planner; LEFT/FULL joins are planned directly (null-extension
        pins probe/build sides). Reference: LookupJoinOperators.java:37
        (innerJoin/probeOuterJoin/lookupOuterJoin/fullOuterJoin)."""
        if rel.kind in ("inner", "cross"):
            terms, conjuncts = self._flatten_from([rel])
            node, scope, g2c = self._join_terms(terms, conjuncts)
            # join reordering permutes the tree's channel layout away
            # from the syntactic scope order; callers (e.g. the probe
            # side of an enclosing LEFT/FULL join) address channels BY
            # SCOPE POSITION, so restore the order with a pass-through
            # projection (fuses into the chain; ColumnRef projections
            # keep dictionary/domain metadata).  Dropping the mapping
            # here mis-bound every predicate above an outer join over a
            # reordered cluster (silent wrong results when types align).
            if any(g2c[i] != i for i in range(len(scope))):
                chans = node.channels
                node = ProjectNode(
                    node,
                    [ColumnRef(type=chans[g2c[i]].type, index=g2c[i])
                     for i in range(len(scope))],
                    [c.name for c in scope.cols],
                )
            return node, scope
        assert rel.kind in ("left", "full"), rel.kind
        lnode, lscope = self._plan_relation(rel.left)
        rnode, rscope = self._plan_relation(rel.right)
        glob = lscope.concat(rscope)
        lkeys: List[Expr] = []
        rkeys: List[Expr] = []
        post: List[Expr] = []
        for c in split_conjuncts(rel.on):
            ir = self._bind(c, glob)
            refs = expr_refs(ir)
            left_refs = [r for r in refs if r < len(lscope)]
            right_refs = [r for r in refs if r >= len(lscope)]
            if (
                isinstance(ir, Call) and ir.fn == "eq"
                and all(isinstance(a, ColumnRef) for a in ir.args)
                and len(left_refs) == 1 and len(right_refs) == 1
            ):
                a, b = ir.args
                if a.index >= len(lscope):
                    a, b = b, a
                lkeys.append(a)
                rkeys.append(ColumnRef(type=b.type, index=b.index - len(lscope)))
            elif not left_refs and rel.kind == "left":
                # right-side-only ON predicate: prefilter build (valid
                # for LEFT joins — unmatched probes still null-extend;
                # NOT valid for FULL, where filtered build rows must
                # still appear null-extended)
                rmap = {r: r - len(lscope) for r in right_refs}
                rnode = FilterNode(rnode, remap_expr(ir, rmap))
            else:
                raise BindError(f"unsupported {rel.kind.upper()} JOIN ON predicate: {c!r}")
        if not lkeys:
            raise BindError(f"{rel.kind.upper()} JOIN requires at least one equi-condition")
        join = JoinNode(
            left=lnode, right=rnode, left_keys=lkeys, right_keys=rkeys,
            kind=rel.kind, unique_build=self._build_is_unique(rnode, rkeys),
        )
        return join, glob

    # ==================================================================
    # join graph (comma FROM + WHERE equi conjuncts)
    # ==================================================================
    def _join_terms(
        self, terms: List[Term], conjunct_asts: List[ast.Node]
    ) -> Tuple[PlanNode, Scope, Dict[int, int]]:
        """Returns (tree, scope, glob->tree channel mapping)."""
        glob = Scope([])
        for t in terms:
            glob = glob.concat(t.scope)

        plain: List[Expr] = []
        for c in conjunct_asts:
            if _is_subquery_conjunct(c):
                self._pending_subqueries.append((c, glob))
                continue
            # (A and X) or (A and Y) -> A and (X or Y): frees common
            # equi-conjuncts (e.g. TPC-H Q19's join key) out of OR
            # blocks so they become join edges instead of a cross join
            # (optimizations/ExtractCommonPredicatesExpressionRewriter)
            plain.extend(_extract_common_or(self._bind(c, glob)))

        def term_of(ref: int) -> int:
            for i, t in enumerate(terms):
                if t.offset <= ref < t.offset + len(t.scope):
                    return i
            raise BindError(
                f"internal: channel reference ${ref} falls outside every "
                "join term's scope (binder channel-offset bug)")

        # route single-term conjuncts as pushed-down filters
        edges: List[Tuple[int, int, Expr]] = []  # (term_i, term_j, eq ir)
        post: List[Expr] = []
        for ir in plain:
            tset = sorted({term_of(r) for r in expr_refs(ir)})
            if len(tset) == 0:
                post.append(ir)  # constant predicate
            elif len(tset) == 1:
                i = tset[0]
                mapping = {r: r - terms[i].offset for r in expr_refs(ir)}
                local = remap_expr(ir, mapping)
                terms[i].node = FilterNode(terms[i].node, local)
                self._push_scan_constraints(terms[i].node, local)
            elif (
                len(tset) == 2
                and isinstance(ir, Call) and ir.fn == "eq"
                and all(isinstance(a, ColumnRef) for a in ir.args)
            ):
                edges.append((tset[0], tset[1], ir))
            else:
                post.append(ir)

        if len(terms) == 1:
            node = terms[0].node
            g2c = {terms[0].offset + i: i for i in range(len(terms[0].scope))}
        elif len(terms) <= 6:
            # cost-based enumeration (ReorderJoins + CostComparator +
            # DetermineJoinDistributionType analog): DP over subsets
            node, g2c = self._cost_based_join(terms, edges, post)
        else:
            node, g2c = self._greedy_join(terms, edges, post)

        for ir in post:
            node = FilterNode(node, remap_expr(ir, g2c))
        return node, glob, g2c

    # nominal worker count for the broadcast-vs-partitioned exchange
    # term of the join cost model (DetermineJoinDistributionType's
    # cost comparison folded into join-order enumeration)
    _COST_WORKERS = 8

    def _cost_based_join(self, terms, edges, post):
        """Selinger-style DP over connected subsets for <=6 relations
        (iterative/rule/ReorderJoins.java + cost/CostComparator.java
        analog).  Each join's cost = build materialization + probe pass
        + output + the cheaper of broadcast / repartitioned exchange —
        so the distribution choice is part of the same comparison.
        Cross joins (no connecting edge) are admitted with their
        Cartesian output as the penalty, keeping disconnected graphs
        and scalar-subquery single-row terms working."""
        from itertools import combinations

        n = len(terms)

        def base_map(i: int):
            return {terms[i].offset + k: k
                    for k in range(len(terms[i].scope))}

        # subset -> (cost, rows, node, g2c, used_edges frozenset)
        best = {}
        for i in range(n):
            rows = max(self._estimate(terms[i].node), 1.0)
            best[frozenset([i])] = (0.0, rows, terms[i].node, base_map(i),
                                    frozenset())

        def join_of(s1, s2):
            """Join best[s1] (probe) with best[s2] (build); returns a
            candidate entry or None."""
            c1, r1, n1, m1, u1 = best[s1]
            c2, r2, n2, m2, u2 = best[s2]
            cross = [k for k, (i, j, _) in enumerate(edges)
                     if k not in u1 and k not in u2
                     and ((i in s1 and j in s2) or (i in s2 and j in s1))]
            lkeys: List[Expr] = []
            rkeys: List[Expr] = []
            for k in cross:
                a, b = edges[k][2].args
                if a.index in m2:  # a on the build side: swap
                    a, b = b, a
                lkeys.append(ColumnRef(type=a.type, index=m1[a.index]))
                rkeys.append(ColumnRef(type=b.type, index=m2[b.index]))
            if not cross:
                zero = Literal(type=BIGINT, value=0)
                lkeys, rkeys = [zero], [zero]
                unique = self._provably_single_row(n2)
            else:
                key_refs = [ColumnRef(type=k.type, index=k.index)
                            for k in rkeys]
                unique = self._build_is_unique(n2, key_refs)
            join = JoinNode(left=n1, right=n2, left_keys=lkeys,
                            right_keys=rkeys, kind="inner",
                            unique_build=unique)
            if not cross:
                out = r1 * r2  # never trust the calculator on lit-keys
            else:
                out = max(self._estimate(join), 1.0)
            exchange = min(self._COST_WORKERS * r2, r1 + r2)
            cost = c1 + c2 + r2 + r1 + out + exchange
            if not cross:
                cost += 2 * out  # Cartesian penalty
            if not unique:
                # non-unique builds run the expanding (materializing)
                # kernel: extra output materialization + a host sync per
                # probe page — strongly prefer streaming orientations
                cost += 2 * (r1 + out)
            g2c = dict(m1)
            off = len(n1.channels)
            for r, idx in m2.items():
                g2c[r] = off + idx
            return (cost, out, join, g2c,
                    u1 | u2 | frozenset(cross))

        idx = list(range(n))
        for size in range(2, n + 1):
            for comb in combinations(idx, size):
                s = frozenset(comb)
                entry = None
                members = sorted(s)
                # enumerate splits; fix the smallest member to one side
                # to halve the symmetric space, but try BOTH probe/build
                # orientations of each split
                rest = [m for m in members if m != members[0]]
                for r_size in range(0, len(rest) + 1):
                    for picked in combinations(rest, r_size):
                        s2 = frozenset(picked) | {members[0]}
                        s1 = s - s2
                        if not s1:
                            continue
                        for probe, build in ((s1, s2), (s2, s1)):
                            cand = join_of(probe, build)
                            if cand is not None and (
                                entry is None or cand[0] < entry[0]
                            ):
                                entry = cand
                best[s] = entry
        cost, rows, node, g2c, used = best[frozenset(idx)]
        for k, (i, j, ir) in enumerate(edges):
            if k not in used:
                post.append(ir)  # cycle edge -> post filter
        return node, g2c

    def _greedy_join(self, terms, edges, post):
        """Probe = largest estimated term; repeatedly hash-join the
        smallest connected term as build side."""
        est = [self._estimate(t.node) for t in terms]
        start = max(range(len(terms)), key=lambda i: est[i])
        joined = {start}
        node = terms[start].node
        g2c = {terms[start].offset + i: i for i in range(len(terms[start].scope))}
        used = [False] * len(edges)
        remaining = set(range(len(terms))) - joined

        while remaining:
            candidates = set()
            for k, (i, j, _) in enumerate(edges):
                if used[k]:
                    continue
                if i in joined and j in remaining:
                    candidates.add(j)
                elif j in joined and i in remaining:
                    candidates.add(i)
            if not candidates:
                # disconnected: cross join smallest remaining term
                pick = min(remaining, key=lambda i: est[i])
                zero = Literal(type=BIGINT, value=0)
                t = terms[pick]
                node = JoinNode(
                    left=node, right=t.node, left_keys=[zero], right_keys=[zero],
                    kind="inner",
                    unique_build=self._provably_single_row(t.node),
                )
                base = len(g2c)
                for li in range(len(t.scope)):
                    g2c[t.offset + li] = base + li
                joined.add(pick)
                remaining.discard(pick)
                continue
            pick = min(candidates, key=lambda i: est[i])
            t = terms[pick]
            lkeys: List[Expr] = []
            rkeys: List[Expr] = []
            for k, (i, j, ir) in enumerate(edges):
                if used[k]:
                    continue
                if (i in joined and j == pick) or (j in joined and i == pick):
                    a, b = ir.args
                    if term_of_ref(terms, a.index) == pick:
                        a, b = b, a
                    lkeys.append(ColumnRef(type=a.type, index=g2c[a.index]))
                    rkeys.append(ColumnRef(type=b.type, index=b.index - t.offset))
                    used[k] = True
            build_unique = self._build_is_unique(t.node, rkeys)
            node = JoinNode(
                left=node, right=t.node, left_keys=lkeys, right_keys=rkeys,
                kind="inner", unique_build=build_unique,
            )
            base = len(g2c)
            for li in range(len(t.scope)):
                g2c[t.offset + li] = base + li
            joined.add(pick)
            remaining.discard(pick)
        # cycle edges (both ends already joined) become post filters
        for k, (i, j, ir) in enumerate(edges):
            if not used[k]:
                post.append(ir)
        return node, g2c

    def _push_scan_constraints(self, node: PlanNode, ir: Expr) -> None:
        """Record simple (col cmp literal) conjuncts on the underlying
        scan for stats-based split pruning (PickTableLayout /
        TupleDomain-pushdown analog)."""
        scan = node
        while isinstance(scan, FilterNode):
            scan = scan.source
        if not isinstance(scan, TableScanNode):
            return
        names = [scan.handle.columns[i].name for i in scan.columns]
        flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}

        def emit(op: str, col: ColumnRef, lit: Literal):
            if lit.value is None:
                return
            if col.type.is_string:
                # dictionary columns: EQUALITY pushes as a code point
                # constraint (split stats for varchar are code min/max;
                # code ORDER is arbitrary, so ranges stay un-pushed).
                # This is what prunes warehouse partitions on string
                # partition columns.
                if op != "eq" or col.type.is_raw_string:
                    return
                ch = scan.handle.columns[scan.columns[col.index]]
                if ch.dictionary is None:
                    return
                try:
                    code = list(ch.dictionary.values).index(lit.value)
                except ValueError:
                    code = -1  # absent value: every split prunes
                scan.constraints.append((names[col.index], "eq", code))
                return
            scan.constraints.append((names[col.index], op, lit.value))

        def walk(e: Expr):
            if not isinstance(e, Call):
                return
            if e.fn == "and":
                walk(e.args[0])
                walk(e.args[1])
                return
            if e.fn in ("eq", "lt", "le", "gt", "ge") and len(e.args) == 2:
                a, b = e.args
                if isinstance(a, ColumnRef) and isinstance(b, Literal):
                    emit(e.fn, a, b)
                elif isinstance(b, ColumnRef) and isinstance(a, Literal):
                    emit(flip[e.fn], b, a)
            elif e.fn == "between" and isinstance(e.args[0], ColumnRef):
                if isinstance(e.args[1], Literal):
                    emit("ge", e.args[0], e.args[1])
                if isinstance(e.args[2], Literal):
                    emit("le", e.args[0], e.args[2])

        walk(ir)

    # ------------------------------------------------------------------
    def _estimate(self, node: PlanNode) -> float:
        """Estimated output rows, via the stats calculator
        (cost/StatsCalculator.java analog, planner/stats.py)."""
        return self._stats.rows(node)

    def _provably_single_row(self, node: PlanNode) -> bool:
        """True only when the node is STRUCTURALLY guaranteed to emit
        at most one row — a global aggregation, a one-row VALUES, or
        LIMIT 1.  Never from cardinality estimates: unique_build is a
        correctness property (the streaming kernel keeps first matches
        only), and an estimate of 0-1 rows can be wrong."""
        n = node
        while isinstance(n, (ProjectNode, OutputNode)):
            n = n.source
        if isinstance(n, AggregationNode):
            return not n.group_exprs
        if isinstance(n, ValuesNode):
            return len(n.rows) <= 1
        if isinstance(n, LimitNode):
            return n.count <= 1
        return False

    def _build_is_unique(self, node: PlanNode, rkeys: Sequence[Expr]) -> bool:
        """True if the build side's join keys are unique: primary-key
        scans or group-by outputs (reference: the planner's knowledge in
        e.g. metadata uniqueness; used to pick the aligned probe kernel)."""
        key_idx = sorted(
            k.index for k in rkeys if isinstance(k, ColumnRef)
        )
        if len(key_idx) != len(rkeys):
            return False
        n = node
        while isinstance(n, (FilterNode, OutputNode)):
            n = n.source
        if isinstance(n, AggregationNode):
            return key_idx == list(range(len(n.group_exprs)))
        if isinstance(n, ProjectNode):
            # project of a PK scan: map refs through bare column projections
            inner_idx = []
            for i in key_idx:
                p = n.projections[i]
                if not isinstance(p, ColumnRef):
                    return False
                inner_idx.append(p.index)
            return self._build_is_unique(n.source, [
                ColumnRef(type=n.projections[i].type, index=j)
                for i, j in zip(key_idx, inner_idx)
            ])
        if isinstance(n, JoinNode):
            # a join that emits at most ONE row per probe row (inner or
            # left against a unique build, or a mark join) preserves
            # probe-side key uniqueness: rows may drop, never duplicate
            if (n.kind in ("mark", "semi", "anti")
                    or (n.kind in ("inner", "left") and n.unique_build)) \
                    and all(i < len(n.left.channels) for i in key_idx):
                return self._build_is_unique(n.left, [
                    ColumnRef(type=n.left.channels[i].type, index=i)
                    for i in key_idx
                ])
            return False
        if isinstance(n, TableScanNode):
            conn = self.catalog.connector(n.handle.connector_name)
            if not hasattr(conn, "primary_key"):
                return False
            pk = conn.primary_key(n.handle.table)
            if pk is None:
                return False
            names = [n.handle.columns[i].name for i in n.columns]
            try:
                pk_idx = sorted(names.index(c) for c in pk)
            except ValueError:
                return False
            return key_idx == pk_idx
        return False

    # ==================================================================
    # query planning
    # ==================================================================
    def _plan_query(self, q: ast.Query) -> Tuple[PlanNode, List[str]]:
        saved_pending = self._pending_subqueries
        saved_windows, saved_slots = self._windows, self._win_slots
        saved_unnests = self._from_unnests
        self._pending_subqueries = []
        self._windows, self._win_slots = [], {}
        self._from_unnests = []
        try:
            return self._plan_query_inner(q, saved_pending)
        finally:
            self._pending_subqueries = saved_pending
            self._windows, self._win_slots = saved_windows, saved_slots
            self._from_unnests = saved_unnests

    def _plan_query_inner(self, q: ast.Query, saved_pending) -> Tuple[PlanNode, List[str]]:
        if q.from_:
            terms, conjuncts = self._flatten_from(q.from_)
            where_cs = split_conjuncts(q.where)
            deferred_cs: List[ast.Node] = []
            if self._from_unnests:
                # WHERE conjuncts over unnest output columns apply after
                # the expansion; name-resolvability against the pre-unnest
                # scope decides placement (no side effects)
                preview = Scope([])
                for t in terms:
                    preview = preview.concat(t.scope)
                kept = []
                for c in where_cs:
                    if not self._names_resolvable(c, preview):
                        deferred_cs.append(c)
                    else:
                        kept.append(c)
                where_cs = kept
            conjuncts = conjuncts + where_cs
            drop_dummy = False
            if not terms and self._from_unnests:
                # FROM UNNEST(...) with no other relation: expand
                # against a synthetic one-row VALUES term (the
                # reference plans a lone Unnest over a single-row
                # source the same way); the hidden channel is
                # projected away after the expansion
                dummy = ValuesNode(names=["$dummy"], types=[BIGINT],
                                   rows=[(0,)])
                terms = [Term(dummy, Scope(
                    [ScopeCol(None, "$dummy", dummy.channels[0])]))]
                drop_dummy = True
            node, glob, g2c = self._join_terms(terms, conjuncts)
            scope = Scope(
                [glob.cols[g] for g, _ in sorted(g2c.items(), key=lambda kv: kv[1])]
            )
            unnests = self._from_unnests
            self._from_unnests = []
            for un in unnests:
                node, scope = self._apply_unnest(node, scope, un)
            if drop_dummy:
                chans = node.channels
                node = ProjectNode(
                    node,
                    [ColumnRef(type=c.type, index=i)
                     for i, c in enumerate(chans)][1:],
                    [c.name for c in chans[1:]],
                )
                scope = Scope(scope.cols[1:])
                g2c = {}
            for c in deferred_cs:
                if _is_subquery_conjunct(c):
                    ident = {i: i for i in range(len(scope))}
                    node, scope = self._apply_subquery_conjunct(
                        node, scope, ident, c, scope)
                else:
                    node = FilterNode(node, self._bind(c, scope))
        else:
            node = ValuesNode(names=["$dummy"], types=[BIGINT], rows=[(0,)])
            scope = Scope([])
            g2c = {}

        # subquery conjuncts (IN/EXISTS/scalar comparisons) -> joins
        pending = self._pending_subqueries
        self._pending_subqueries = []
        for c, cglob in pending:
            node, scope = self._apply_subquery_conjunct(node, scope, g2c, c, cglob)
        self._pending_subqueries = saved_pending

        # scalar subqueries in SELECT position (uncorrelated): each
        # plans standalone and cross-joins its single row onto the
        # relation; the expression binder resolves the original AST
        # node to the appended channel (TPC-DS q9's CASE-over-counts
        # shape; reference: SubqueryPlanner's apply of uncorrelated
        # scalars).  Aggregated outer queries keep the restriction.
        select_scalar_subs: List[ast.Node] = []
        for it in q.select:
            if not isinstance(it.expr, ast.Star):
                _find_scalar_subqueries(it.expr, select_scalar_subs)
        select_sub_ids: List[int] = []
        try:
            for sq in select_scalar_subs:
                sub_node, _ = self._plan_query_like(sq.query)
                ref = ColumnRef(type=sub_node.channels[0].type,
                                index=len(node.channels))
                node = CrossSingleNode(left=node, right=sub_node)
                self._scalar_refs[id(sq)] = ref
                select_sub_ids.append(id(sq))
        except BindError:
            for k in select_sub_ids:
                self._scalar_refs.pop(k, None)
            raise

        # select list expansion
        items: List[Tuple[ast.Node, str]] = []
        for it in q.select:
            if isinstance(it.expr, ast.Star):
                for sc in scope.cols:
                    if it.expr.qualifier is None or sc.qualifier == it.expr.qualifier:
                        items.append((ast.Identifier((sc.qualifier, sc.name) if sc.qualifier else (sc.name,)), sc.name))
            else:
                items.append((it.expr, it.alias or self._derive_name(it.expr)))

        group_asts = list(q.group_by)
        # ordinal group-by ("GROUP BY 1")
        group_asts = [
            items[int(g.text) - 1][0] if isinstance(g, ast.NumberLit) else g
            for g in group_asts
        ]
        grouping_sets = None
        expanded = self._expand_grouping(group_asts)
        if expanded is not None:
            group_asts, grouping_sets = expanded
        has_aggs = bool(group_asts) or grouping_sets is not None or any(
            self._contains_agg(e) for e, _ in items
        ) or (q.having is not None and self._contains_agg(q.having))

        order_items = list(q.order_by)
        if order_items:
            # ORDER BY may reference select aliases INSIDE expressions
            # (e.g. CASE WHEN lochierarchy = 0 THEN ... — TPC-DS
            # q36/q70/q86); substitute the aliased expression wherever
            # the name does not resolve as a real column
            alias_map = {n: se for se, n in items
                         if not isinstance(se, ast.Star)}
            order_items = [
                dataclasses.replace(
                    o, expr=self._substitute_aliases(o.expr, alias_map, scope))
                for o in order_items
            ]

        if has_aggs:
            if select_sub_ids:
                for k in select_sub_ids:
                    self._scalar_refs.pop(k, None)
                raise BindError(
                    "scalar subquery in the SELECT of an aggregating "
                    "query unsupported")
            node, out_irs, names, order_irs = self._plan_aggregation(
                node, scope, items, group_asts, q.having, order_items,
                grouping_sets=grouping_sets,
            )
        else:
            if q.having is not None:
                raise BindError("HAVING without aggregation")
            try:
                out_irs = [self._bind(e, scope) for e, _ in items]
            finally:
                for k in select_sub_ids:
                    self._scalar_refs.pop(k, None)
            names = [n for _, n in items]
            order_irs = self._bind_order(order_items, items, out_irs, scope)

        # windows sit above aggregation/having; patch sentinel refs to
        # real appended channels
        if self._windows:
            node, win_map = self._attach_windows(node)
            out_irs = [self._patch_windows(ir, win_map) for ir in out_irs]
            order_irs = [self._patch_windows(ir, win_map) for ir in order_irs]

        node = ProjectNode(node, out_irs + [ir for ir in order_irs if ir not in out_irs],
                           names + [f"$order{i}" for i, ir in enumerate(order_irs) if ir not in out_irs])
        # order exprs as channel refs over the project output
        order_channels: List[ColumnRef] = []
        for ir in order_irs:
            idx = node.projections.index(ir)
            order_channels.append(ColumnRef(type=ir.type, index=idx))

        if q.distinct:
            node = AggregationNode(
                node,
                [ColumnRef(type=c.type, index=i) for i, c in enumerate(node.channels)],
                node.output_names,
                [], [],
                max_groups=self._distinct_capacity(node),
            )

        if order_items:
            asc = [o.ascending for o in order_items]
            nf = [o.nulls_first if o.nulls_first is not None else (not o.ascending) for o in order_items]
            if q.limit is not None:
                node = TopNNode(node, order_channels, asc, q.limit, nf)
            else:
                node = SortNode(node, order_channels, asc, nf)
        elif q.limit is not None:
            node = LimitNode(node, q.limit)

        if len(node.channels) > len(names):  # drop hidden order-by channels
            node = ProjectNode(
                node,
                [ColumnRef(type=c.type, index=i) for i, c in enumerate(node.channels[: len(names)])],
                names,
            )
        return node, names

    def _expand_grouping(self, group_by) -> Optional[Tuple[List[ast.Node], List[List[int]]]]:
        """Expand ROLLUP/CUBE/GROUPING SETS group-by items into
        (full key list, grouping sets as key-index lists); None for plain
        GROUP BY. Mixed items combine by cartesian concatenation, the
        reference's semantics (sql/analyzer/StatementAnalyzer.java
        analyzeGroupBy: cross product of grouping-element sets)."""
        comps: List[List[Tuple[ast.Node, ...]]] = []
        plain = True
        for g in group_by:
            if isinstance(g, ast.Rollup):
                comps.append([tuple(g.items[:i]) for i in range(len(g.items), -1, -1)])
                plain = False
            elif isinstance(g, ast.Cube):
                sets = []
                for bits in range(1 << len(g.items)):
                    sets.append(tuple(e for i, e in enumerate(g.items) if bits & (1 << i)))
                comps.append(sets)
                plain = False
            elif isinstance(g, ast.GroupingSets):
                comps.append([tuple(s) for s in g.sets])
                plain = False
            else:
                comps.append([(g,)])
        if plain:
            return None
        combined: List[Tuple[ast.Node, ...]] = [()]
        for sets in comps:
            combined = [c + s for c in combined for s in sets]
        full: List[ast.Node] = []
        for s in combined:
            for e in s:
                if e not in full:
                    full.append(e)
        sets_idx = [sorted({full.index(e) for e in s}) for s in combined]
        return full, sets_idx

    def _distinct_capacity(self, node: PlanNode) -> int:
        est = int(self._estimate(node))
        return max(1 << 10, min(1 << (max(est - 1, 1)).bit_length(), 1 << 24))

    def _derive_name(self, e: ast.Node) -> str:
        if isinstance(e, ast.Identifier):
            return e.name
        if isinstance(e, ast.FuncCall):
            return e.name
        return "_col"

    def _contains_agg(self, e: ast.Node) -> bool:
        if isinstance(e, ast.WindowExpr):
            # a window function is NOT an aggregate query trigger —
            # only aggregates nested inside its arguments are
            return (
                any(self._contains_agg(a) for a in e.func.args)
                or any(self._contains_agg(p) for p in e.partition_by)
                or any(self._contains_agg(o.expr) for o in e.order_by)
            )
        if isinstance(e, ast.FuncCall) and e.name in AGG_FUNCTIONS:
            return True
        for f in dataclasses.fields(e) if dataclasses.is_dataclass(e) else []:
            v = getattr(e, f.name)
            for x in v if isinstance(v, tuple) else [v]:
                if isinstance(x, ast.Node) and not isinstance(x, ast.Query) and self._contains_agg(x):
                    return True
        return False

    # ------------------------------------------------------------------
    def _plan_aggregation(self, node, scope, items, group_asts, having, order_items,
                          grouping_sets=None):
        group_irs = [self._bind(g, scope) for g in group_asts]
        if grouping_sets is not None:
            # GROUPING SETS: replicate rows per set via GroupIdNode and
            # aggregate once grouped by (keys..., $group_id); inactive
            # keys are NULL-masked so each set groups independently.
            nsrc = len(scope)
            key_names = [self._derive_name(g) for g in group_asts]
            masks = [[i in s for i in range(len(group_asts))] for s in grouping_sets]
            node = GroupIdNode(node, group_irs, key_names, masks)
            group_irs = [
                ColumnRef(type=g.type, index=nsrc + i, name=key_names[i])
                for i, g in enumerate(group_irs)
            ] + [ColumnRef(type=BIGINT, index=nsrc + len(group_asts), name="$group_id")]
        agg_ctx = AggCtx(group_asts=group_asts, group_irs=group_irs,
                         set_masks=masks if grouping_sets is not None else None)

        out_irs = [self._bind_agg(e, scope, agg_ctx) for e, _ in items]
        names = [n for _, n in items]

        # HAVING: plain conjuncts filter the agg output; conjuncts with
        # scalar subqueries ANYWHERE in the expression — bare (Q11) or
        # nested in arithmetic (TPC-DS q44's avg(x) > 0.9 * (select …))
        # — plan each subquery as a single-row cross join and bind the
        # subquery positions to negative sentinel refs, remapped to the
        # spliced cross-join channels after the aggregation is built.
        having_plain: List[Expr] = []
        having_sub: List[Tuple[Expr, List[PlanNode], bool]] = []
        for c in split_conjuncts(having):
            negated = False
            while isinstance(c, ast.Unary) and c.op == "not":
                negated = not negated
                c = c.operand
            subs: List[ast.Node] = []
            _find_scalar_subqueries(c, subs)
            if subs:
                planned: List[PlanNode] = []
                for k, sq in enumerate(subs):
                    sub_node, _ = self._plan_query_like(sq.query)
                    self._scalar_refs[id(sq)] = ColumnRef(
                        type=sub_node.channels[0].type, index=-(k + 1))
                    planned.append(sub_node)
                try:
                    ir = self._bind_agg(c, scope, agg_ctx)
                finally:
                    for sq in subs:
                        self._scalar_refs.pop(id(sq), None)
                having_sub.append((ir, planned, negated))
            elif _is_subquery_conjunct(c):
                raise BindError(
                    "only scalar subqueries are supported in HAVING")
            else:
                ir = self._bind_agg(c, scope, agg_ctx)
                having_plain.append(call("not", ir) if negated else ir)
        order_irs = []
        for o in order_items:
            e = o.expr
            if isinstance(e, ast.NumberLit):  # ordinal
                order_irs.append(out_irs[int(e.text) - 1])
                continue
            # select alias?
            alias_hit = next(
                (out_irs[i] for i, (se, n) in enumerate(items) if isinstance(e, ast.Identifier) and e.name == n),
                None,
            )
            if alias_hit is not None:
                order_irs.append(alias_hit)
            else:
                order_irs.append(self._bind_agg(e, scope, agg_ctx))

        group_names = [self._derive_name(g) for g in group_asts]
        if grouping_sets is not None:
            group_names = group_names + ["$group_id"]
        agg_names = [f"$agg{j}" for j in range(len(agg_ctx.aggs))]

        # approx_percentile: exact-rank rewrite through a window pre-pass
        if any(a.fn == "approx_percentile" for a in agg_ctx.aggs):
            node = self._rewrite_approx_percentile(node, group_irs, agg_ctx)

        # histogram: two-level rewrite (inner per-value counts, outer
        # map_agg) — HistogramAggregation analog
        if any(a.fn == "histogram" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_histogram(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # approx_distinct: HyperLogLog two-level aggregation rewrite
        if any(a.fn == "approx_distinct" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_approx_distinct(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # multimap_agg: inner per-(keys, k) array_agg(v), outer scatter
        if any(a.fn == "multimap_agg" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_multimap(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # numeric_histogram: window min/max span -> fixed-width bins
        if any(a.fn == "numeric_histogram" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_numeric_histogram(
                node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # approx_set: two-level HLL rewrite materializing the sketch
        if any(a.fn == "approx_set" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_approx_set(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # merge(hll): unnest sketch registers, per-bucket max, re-sketch
        if any(a.fn == "merge" for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_hll_union(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        # distinct aggregates: rewrite through a distinct pre-aggregation
        if any(a.distinct for a in agg_ctx.aggs):
            node, agg_ctx = self._rewrite_distinct_aggs(node, scope, group_irs, agg_ctx)
            group_irs = agg_ctx.group_irs

        est = self._estimate(node)
        agg = AggregationNode(
            node, group_irs, group_names, agg_ctx.aggs, agg_names,
            max_groups=self._group_capacity(group_irs, scope, est, node=node),
            presorted=self._input_presorted(node, group_irs),
        )
        out: PlanNode = agg
        for ir in having_plain:
            out = FilterNode(out, ir)
        for ir, planned, negated in having_sub:
            mapping = {r: r for r in expr_refs(ir) if r >= 0}
            for k, sub_node in enumerate(planned):
                mapping[-(k + 1)] = len(out.channels)
                out = CrossSingleNode(left=out, right=sub_node)
            pred = remap_expr(ir, mapping)
            if negated:
                pred = call("not", pred)
            out = FilterNode(out, pred)
        return out, out_irs, names, order_irs

    def _group_capacity(self, group_irs: List[Expr], scope: Scope, est_rows: float,
                        node: Optional[PlanNode] = None) -> int:
        """Initial group capacity from domains / NDV stats; the executor
        doubles (or spills) on overflow, so this is a starting size, not
        a correctness bound."""
        if not group_irs:
            return 1
        prod = 1.0
        for g in group_irs:
            ndv = None
            if isinstance(g, ColumnRef):
                if node is not None:
                    ndv = self._stats.estimate(node).col(g.index).ndv
                if ndv is None and g.index < len(scope.cols) \
                        and scope.cols[g.index].channel.domain is not None:
                    lo, hi = scope.cols[g.index].channel.domain
                    ndv = float(hi - lo + 2)
            if ndv is None:
                prod = float(1 << 60)
                break
            prod *= max(ndv, 1.0)
        cap = int(min(prod, est_rows + 1))
        cap = 1 << (max(cap - 1, 1)).bit_length()
        return max(1 << 4, min(cap, 1 << 24))

    def _rewrite_approx_percentile(self, node, group_irs, agg_ctx: AggCtx):
        """approx_percentile(x, p) -> max(if(rn = floor(p*(cnt-1))+1, x))
        over a window pre-pass computing rn = row_number() and cnt =
        count(x) per group partition ordered by x. Exact rank selection
        (better than the reference's qdigest approximation,
        operator/aggregation/ApproximateLongPercentileAggregations.java)
        expressed with existing segmented-scan machinery — no sketch
        state to merge. Entries are replaced in place so already-bound
        output references stay valid."""
        from presto_tpu.ops.window import WindowFunc
        from presto_tpu.planner.plan import WindowNode

        win_cache: Dict[tuple, tuple] = {}  # (x, w) -> channel refs
        for j, a in enumerate(list(agg_ctx.aggs)):
            if a.fn != "approx_percentile":
                continue
            if a.distinct:
                raise BindError("approx_percentile DISTINCT unsupported")
            x, p = a.arg, a.arg2
            cache_key = (x, a.arg3, a.filter)
            base = len(node.channels)
            if a.arg3 is not None:
                # weighted: smallest x whose running weight (ordered by
                # x) reaches p * total weight — exact weighted rank
                # selection via a running-sum window (one window pass
                # per distinct (x, w) spec, shared by ARRAY fractions)
                from presto_tpu.ops.window import WindowFunc
                from presto_tpu.planner.plan import WindowNode

                if cache_key in win_cache:
                    cw, tw = win_cache[cache_key]
                    hit = call("ge", cw, call("mul", p, tw))
                    newarg = call("if", hit, x,
                                  Literal(type=x.type, value=None))
                    agg_ctx.aggs[j] = AggCall(fn="min", arg=newarg,
                                              type=a.type, filter=a.filter)
                    continue

                w = call("cast_double", a.arg3) \
                    if a.arg3.type.name != "double" else a.arg3
                # rows the aggregate ignores (NULL x, FILTER-excluded)
                # must not contribute weight to the running/total sums
                counted = call("not_null", x)
                if a.filter is not None:
                    counted = call("and", counted, a.filter)
                w = call("if", counted, w, Literal(type=DOUBLE, value=0.0))
                node = WindowNode(
                    source=node, partition_exprs=list(group_irs),
                    order_exprs=[x], ascending=[True],
                    funcs=[WindowFunc(kind="sum", arg=w),
                           WindowFunc(kind="sum", arg=w, frame=("whole",))],
                    func_names=[f"$pctl_cw{j}", f"$pctl_tw{j}"],
                )
                cw = ColumnRef(type=DOUBLE, index=base)
                tw = ColumnRef(type=DOUBLE, index=base + 1)
                win_cache[cache_key] = (cw, tw)
                hit = call("ge", cw, call("mul", p, tw))
                newarg = call("if", hit, x, Literal(type=x.type, value=None))
                agg_ctx.aggs[j] = AggCall(fn="min", arg=newarg, type=a.type,
                                          filter=a.filter)
                continue
            if cache_key in win_cache:
                rn_ref, cnt_ref = win_cache[cache_key]
            else:
                node = WindowNode(
                    source=node,
                    partition_exprs=list(group_irs),
                    order_exprs=[x],
                    ascending=[True],
                    funcs=[WindowFunc(kind="row_number"),
                           WindowFunc(kind="count", arg=x, frame=("whole",))],
                    func_names=[f"$pctl_rn{j}", f"$pctl_cnt{j}"],
                )
                rn_ref = ColumnRef(type=BIGINT, index=base)
                cnt_ref = ColumnRef(type=BIGINT, index=base + 1)
                win_cache[cache_key] = (rn_ref, cnt_ref)
            target = call(
                "add",
                call("cast_bigint",
                     call("floor",
                          call("mul", p,
                               call("cast_double",
                                    call("sub", cnt_ref, Literal(type=BIGINT, value=1)))))),
                Literal(type=BIGINT, value=1),
            )
            newarg = call("if", call("eq", rn_ref, target), x,
                          Literal(type=x.type, value=None))
            agg_ctx.aggs[j] = AggCall(fn="max", arg=newarg, type=a.type,
                                      filter=a.filter)
        return node

    def _rewrite_histogram(self, node, scope, group_irs, agg_ctx: AggCtx):
        """histogram(x) -> inner aggregation grouped by (keys..., x)
        computing count(*), outer map_agg(x, count)
        (operator/aggregation/histogram/Histogram.java realized through
        the engine's own container machinery)."""
        if not all(a.fn == "histogram" for a in agg_ctx.aggs):
            raise BindError("histogram cannot mix with other aggregates")
        args = {a.arg for a in agg_ctx.aggs}
        if len(args) != 1:
            raise BindError("multiple histogram arguments unsupported")
        (arg,) = args
        inner_keys = group_irs + [arg]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [AggCall(fn="count_star", arg=None, type=BIGINT)], ["$cnt"],
            max_groups=self._group_capacity(
                inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i) for i, g in enumerate(group_irs)]
        x_ref = ColumnRef(type=arg.type, index=len(group_irs))
        cnt_ref = ColumnRef(type=BIGINT, index=len(inner_keys))
        from presto_tpu.ops.aggregate import output_type as _agg_out

        proto = AggCall(fn="map_agg", arg=x_ref, type=arg.type, arg2=cnt_ref)
        new_aggs = [dataclasses.replace(proto, type=_agg_out(proto))
                    for _ in agg_ctx.aggs]
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group,
                     aggs=new_aggs)
        return inner, ctx

    def _rewrite_approx_distinct(self, node, scope, group_irs, agg_ctx: AggCtx):
        """approx_distinct(x) -> inner aggregation grouped by
        (keys..., hll_bucket(x)) computing max(hll_rho(x)), outer
        hll_merge folding the per-bucket registers into the HLL
        estimate. Reference: ApproximateCountDistinctAggregations.java
        (airlift HyperLogLog); here the register file IS the inner
        aggregation's output — no per-group register arrays."""
        if not all(a.fn == "approx_distinct" for a in agg_ctx.aggs):
            raise BindError("approx_distinct cannot mix with other aggregates")
        args = {a.arg for a in agg_ctx.aggs}
        if len(args) != 1:
            raise BindError("multiple approx_distinct arguments unsupported")
        (arg,) = args
        inner_keys = group_irs + [call("hll_bucket", arg)]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [AggCall(fn="max", arg=call("hll_rho", arg), type=BIGINT)], ["$rho"],
            max_groups=self._group_capacity(inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i) for i, g in enumerate(group_irs)]
        rho_ref = ColumnRef(type=BIGINT, index=len(inner_keys))
        new_aggs = [AggCall(fn="hll_merge", arg=rho_ref, type=BIGINT)
                    for _ in agg_ctx.aggs]
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group, aggs=new_aggs)
        return inner, ctx

    def _rewrite_multimap(self, node, scope, group_irs, agg_ctx: AggCtx):
        """multimap_agg(k, v) -> inner aggregation grouped by
        (keys..., k) computing array_agg(v), outer scatter of
        (k, array) pairs into a MAP(K, ARRAY(V)) value (reference:
        MultimapAggregationFunction; the nested value lanes stay fixed
        matrices so the scatter is one 2-D gather)."""
        if not all(a.fn == "multimap_agg" for a in agg_ctx.aggs):
            raise BindError("multimap_agg cannot mix with other aggregates")
        pairs = {(a.arg, a.arg2) for a in agg_ctx.aggs}
        if len(pairs) != 1:
            raise BindError("multiple multimap_agg argument pairs unsupported")
        ((karg, varg),) = pairs
        inner_keys = group_irs + [karg]
        from presto_tpu.ops.aggregate import output_type as _agg_out

        arr_proto = AggCall(fn="array_agg", arg=varg, type=varg.type)
        arr_proto = dataclasses.replace(arr_proto, type=_agg_out(arr_proto))
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [arr_proto], ["$vals"],
            max_groups=self._group_capacity(
                inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i)
                     for i, g in enumerate(group_irs)]
        k_ref = ColumnRef(type=karg.type, index=len(group_irs))
        arr_ref = ColumnRef(type=arr_proto.type, index=len(inner_keys))
        proto = AggCall(fn="multimap_agg", arg=k_ref, type=karg.type,
                        arg2=arr_ref)
        proto = dataclasses.replace(proto, type=_agg_out(proto))
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group,
                     aggs=[proto for _ in agg_ctx.aggs])
        return inner, ctx

    def _rewrite_numeric_histogram(self, node, scope, group_irs,
                                   agg_ctx: AggCtx):
        """numeric_histogram(b, x) -> window (min/max of x per group)
        -> bin index -> inner per-(keys, bin) avg(x) + count ->
        outer map_agg(mean, count) as MAP(DOUBLE, DOUBLE)
        (NumericHistogramAggregation's role: per-bin centroids and
        weights; fixed-width bins over the group's span instead of the
        reference's streaming Ben-Haim/Tom-Tov merges)."""
        from presto_tpu.ops.window import WindowFunc
        from presto_tpu.planner.plan import WindowNode
        from presto_tpu.ops.aggregate import output_type as _agg_out

        if not all(a.fn == "numeric_histogram" for a in agg_ctx.aggs):
            raise BindError(
                "numeric_histogram cannot mix with other aggregates")
        pairs = {(a.arg, a.arg2.value) for a in agg_ctx.aggs}
        if len(pairs) != 1:
            raise BindError("multiple numeric_histogram arguments unsupported")
        ((arg, nb),) = pairs
        nb = int(nb)
        base = len(node.channels)
        x = call("cast_double", arg) if arg.type.name != "double" else arg
        node = WindowNode(
            source=node, partition_exprs=list(group_irs), order_exprs=[],
            ascending=[],
            funcs=[WindowFunc(kind="min", arg=x, frame=("whole",)),
                   WindowFunc(kind="max", arg=x, frame=("whole",))],
            func_names=["$nh_min", "$nh_max"],
        )
        mn = ColumnRef(type=DOUBLE, index=base)
        mx = ColumnRef(type=DOUBLE, index=base + 1)
        width = call("div", call("sub", mx, mn),
                     Literal(type=DOUBLE, value=float(nb)))
        safe_w = call("if", call("gt", width, Literal(type=DOUBLE, value=0.0)),
                      width, Literal(type=DOUBLE, value=1.0))
        bidx = call("least",
                    call("cast_bigint",
                         call("floor", call("div", call("sub", x, mn), safe_w))),
                    Literal(type=BIGINT, value=nb - 1))
        inner_keys = group_irs + [bidx]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [AggCall(fn="avg", arg=x, type=DOUBLE),
             AggCall(fn="count", arg=x, type=BIGINT)],
            ["$mean", "$cnt"],
            max_groups=self._group_capacity(
                inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i)
                     for i, g in enumerate(group_irs)]
        mean_ref = ColumnRef(type=DOUBLE, index=len(inner_keys))
        cnt_ref = call("cast_double",
                       ColumnRef(type=BIGINT, index=len(inner_keys) + 1))
        proto = AggCall(fn="map_agg", arg=mean_ref, type=DOUBLE, arg2=cnt_ref)
        proto = dataclasses.replace(proto, type=_agg_out(proto))
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group,
                     aggs=[proto for _ in agg_ctx.aggs])
        return inner, ctx

    def _rewrite_approx_set(self, node, scope, group_irs, agg_ctx: AggCtx):
        """approx_set(x) -> inner aggregation grouped by
        (keys..., hll_bucket(x, P)) computing max(hll_rho(x, P)), outer
        hll_sketch scattering (bucket, rho) into the HYPERLOGLOG map
        value (reference: ApproximateSetAggregation.java producing a
        P4HyperLogLog; here the sketch is the map_agg scatter over
        m = HLL_SET_BUCKETS registers)."""
        from presto_tpu.types import HLL_SET_BUCKETS, HllType

        if not all(a.fn == "approx_set" for a in agg_ctx.aggs):
            raise BindError("approx_set cannot mix with other aggregates")
        args = {a.arg for a in agg_ctx.aggs}
        if len(args) != 1:
            raise BindError("multiple approx_set arguments unsupported")
        (arg,) = args
        p_lit = Literal(type=BIGINT, value=HLL_SET_BUCKETS.bit_length() - 1)
        inner_keys = group_irs + [call("hll_bucket", arg, p_lit)]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [AggCall(fn="max", arg=call("hll_rho", arg, p_lit), type=BIGINT)],
            ["$rho"],
            max_groups=self._group_capacity(
                inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i)
                     for i, g in enumerate(group_irs)]
        bucket_ref = ColumnRef(type=BIGINT, index=len(group_irs))
        rho_ref = ColumnRef(type=BIGINT, index=len(inner_keys))
        new_aggs = [AggCall(fn="hll_sketch", arg=bucket_ref, type=HllType(),
                            arg2=rho_ref)
                    for _ in agg_ctx.aggs]
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group,
                     aggs=new_aggs)
        return inner, ctx

    def _rewrite_hll_union(self, node, scope, group_irs, agg_ctx: AggCtx):
        """merge(sketch) -> unnest each sketch's (bucket, rho) entries,
        per-(keys, bucket) max(rho), re-sketch — HLL union as plain
        relational algebra (reference: MergeHyperLogLogAggregation)."""
        from presto_tpu.planner.plan import UnnestNode
        from presto_tpu.types import HllType

        if not all(a.fn == "merge" for a in agg_ctx.aggs):
            raise BindError("merge cannot mix with other aggregates")
        args = {a.arg for a in agg_ctx.aggs}
        if len(args) != 1:
            raise BindError("multiple merge arguments unsupported")
        (arg,) = args
        if not arg.type.is_hll:
            raise BindError("merge() expects a HYPERLOGLOG argument "
                            "(approx_set output)")
        base = len(node.channels)
        node = UnnestNode(node, [arg], ["$hbucket", "$hrho"])
        bucket_col = ColumnRef(type=BIGINT, index=base)
        rho_col = ColumnRef(type=BIGINT, index=base + 1)
        inner_keys = group_irs + [bucket_col]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            [AggCall(fn="max", arg=rho_col, type=BIGINT)], ["$rho"],
            max_groups=self._group_capacity(
                inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i)
                     for i, g in enumerate(group_irs)]
        bucket_ref = ColumnRef(type=BIGINT, index=len(group_irs))
        rho_ref = ColumnRef(type=BIGINT, index=len(inner_keys))
        new_aggs = [AggCall(fn="hll_sketch", arg=bucket_ref, type=HllType(),
                            arg2=rho_ref)
                    for _ in agg_ctx.aggs]
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group,
                     aggs=new_aggs)
        return inner, ctx

    # non-distinct aggregates that survive the two-level distinct
    # rewrite: inner per-(g, x) value re-aggregated by the outer fn
    # count re-aggregates through sum0 (sum with 0-on-empty): a plain
    # count must stay 0, never NULL, over empty input
    _DECOMPOSABLE_OUTER = {"sum": "sum", "count": "sum0",
                           "count_star": "sum0", "min": "min", "max": "max"}

    def _rewrite_distinct_aggs(self, node, scope, group_irs, agg_ctx: AggCtx):
        """agg(DISTINCT x) GROUP BY g  ->  inner group on (g, x), outer
        re-aggregation (MarkDistinct /
        MultipleDistinctAggregationToMarkDistinct analog).  All DISTINCT
        aggregates must share one argument; non-distinct aggregates mix
        in when they are decomposable (sum/count/min/max): the inner
        level computes them per (g, x) and the outer level re-combines
        (count(distinct o) + sum(cost) — the TPC-DS q16/q95 shape)."""
        distinct_args = {a.arg for a in agg_ctx.aggs if a.distinct}
        if len(distinct_args) != 1:
            raise BindError("mixed/multi-arg DISTINCT aggregates unsupported")
        plain = [a for a in agg_ctx.aggs if not a.distinct]
        if not all(a.fn in self._DECOMPOSABLE_OUTER for a in plain):
            raise BindError(
                "DISTINCT aggregates mix only with sum/count/min/max")
        (arg,) = distinct_args
        inner_keys = group_irs + [arg]
        inner = AggregationNode(
            node, inner_keys, [f"$k{i}" for i in range(len(inner_keys))],
            list(plain), [f"$p{i}" for i in range(len(plain))],
            max_groups=self._group_capacity(inner_keys, scope, self._estimate(node), node=node),
        )
        new_group = [ColumnRef(type=g.type, index=i) for i, g in enumerate(group_irs)]
        arg_ref = ColumnRef(type=arg.type, index=len(group_irs))
        inner_out = inner.channels
        new_aggs = []
        plain_pos = 0
        for a in agg_ctx.aggs:
            if a.distinct:
                new_aggs.append(
                    AggCall(fn=a.fn, arg=arg_ref, type=a.type, distinct=False))
            else:
                ref = ColumnRef(
                    type=inner_out[len(inner_keys) + plain_pos].type,
                    index=len(inner_keys) + plain_pos,
                )
                new_aggs.append(AggCall(fn=self._DECOMPOSABLE_OUTER[a.fn],
                                        arg=ref, type=a.type))
                plain_pos += 1
        ctx = AggCtx(group_asts=agg_ctx.group_asts, group_irs=new_group, aggs=new_aggs)
        return inner, ctx

    # ==================================================================
    # subquery conjuncts
    # ==================================================================
    def _apply_subquery_conjunct(self, node, scope, g2c, c: ast.Node, glob: Scope):
        negated = False
        while isinstance(c, ast.Unary) and c.op == "not":
            negated = not negated
            c = c.operand

        remap = dict(g2c)

        if isinstance(c, ast.InSubquery):
            if self._is_correlated(c.query, glob):
                # correlated IN: x IN (select y from t where corr) ==
                # EXISTS (select 1 from t where corr and y = x) — the
                # membership equality becomes one more correlation
                # equi-conjunct (TransformCorrelatedInPredicateToJoin)
                q = c.query
                if len(q.select) != 1 or isinstance(q.select[0].expr, ast.Star):
                    raise BindError("IN subquery must select one column")
                if q.group_by or q.having or q.limit is not None \
                        or self._contains_agg(q.select[0].expr):
                    raise BindError(
                        "correlated IN over an aggregated/limited subquery "
                        "is unsupported")
                eq = ast.Binary("=", q.select[0].expr, c.value)
                new_where = eq if q.where is None else \
                    ast.Binary("and", q.where, eq)
                q2 = dataclasses.replace(
                    q, select=(ast.SelectItem(ast.NumberLit("1"), None),),
                    where=new_where)
                kind = "anti" if (negated ^ c.negated) else "semi"
                return self._plan_exists(node, scope, remap, glob, q2, kind)
            sub, sub_names = self._plan_query_like(c.query)
            value_ir = remap_expr(self._bind(c.value, glob), remap)
            kind = "anti" if (negated ^ c.negated) else "semi"
            join = JoinNode(
                left=node, right=sub,
                left_keys=[value_ir],
                right_keys=[ColumnRef(type=sub.channels[0].type, index=0)],
                kind=kind,
                null_aware=True,  # ANSI three-valued IN/NOT IN
            )
            return join, scope

        if isinstance(c, ast.Exists):
            kind = "anti" if (negated ^ c.negated) else "semi"
            return self._plan_exists(node, scope, remap, glob, c.query, kind)

        contained_marks: List[ast.Node] = []
        _find_mark_subqueries(c, contained_marks)
        if isinstance(c, ast.Binary) and not contained_marks:
            # the scalar subquery may sit anywhere inside the comparison
            # (e.g. price > 1.2 * (select avg(...))): plan it, bind the
            # conjunct with the subquery replaced by a marker ref, then
            # remap the marker to the planned output channel
            subs: List[ast.Node] = []
            _find_scalar_subqueries(c, subs)
            # one plan per DISTINCT node: the quantified-comparison
            # desugar shares one comparison subtree across CASE whens,
            # so the same ScalarSubquery object can occur repeatedly
            seen_ids = set()
            subs = [sq for sq in subs
                    if not (id(sq) in seen_ids or seen_ids.add(id(sq)))]
            if not subs:
                raise BindError("no scalar subquery found in conjunct")
            # any number of scalar subqueries per conjunct (quantified
            # comparisons desugar to CASEs over min/max + two counts):
            # each plans as a single-row cross join, bound through a
            # distinct marker ref remapped to its spliced channel
            markers: Dict[int, int] = {}
            for j, sq in enumerate(subs):
                node, scope, value_ref = self._plan_scalar_subquery(
                    node, scope, remap, glob, sq.query)
                marker = (1 << 28) + j
                self._scalar_refs[id(sq)] = ColumnRef(
                    type=value_ref.type, index=marker)
                markers[marker] = value_ref.index
            try:
                ir = self._bind(c, glob)
            finally:
                for sq in subs:
                    self._scalar_refs.pop(id(sq), None)
            full_map = dict(remap)
            full_map.update(markers)
            pred = remap_expr(ir, full_map)
            if negated:
                pred = call("not", pred)
            return FilterNode(node, pred), scope

        # General fallback: a boolean expression with EXISTS/IN-subquery
        # operands in arbitrary positions (e.g. OR of two EXISTS — the
        # TPC-DS q10/q35 shape).  Each subquery lowers to a MARK join
        # appending a boolean presence column; the expression then binds
        # with the subquery operands replaced by those columns
        # (the reference's mark semijoin: SemiJoinNode + the rewrite in
        # TransformExistsApplyToLateralNode/MarkDistinct machinery).
        marks: List[ast.Node] = []
        _find_mark_subqueries(c, marks)
        if marks:
            full_map = dict(remap)
            planned: List[int] = []
            try:
                for j, m in enumerate(marks):
                    if isinstance(m, ast.Exists):
                        node, mark_idx = self._plan_exists_mark(
                            node, remap, glob, m.query)
                    else:
                        node, mark_idx = self._plan_in_mark(node, remap, glob, m)
                    marker = (1 << 28) + j
                    from presto_tpu.types import BOOLEAN as _BOOLEAN

                    self._mark_refs[id(m)] = ColumnRef(type=_BOOLEAN, index=marker)
                    planned.append(id(m))
                    full_map[marker] = mark_idx
                ir = self._bind(c, glob)
            finally:
                for key in planned:
                    self._mark_refs.pop(key, None)
            pred = remap_expr(ir, full_map)
            if negated:
                pred = call("not", pred)
            return FilterNode(node, pred), scope

        raise BindError(f"unsupported subquery conjunct {c!r}")

    def _plan_exists_mark(self, node, remap, glob, q):
        """EXISTS as a mark join: returns (new node, channel index of
        the boolean presence column)."""
        if isinstance(q, ast.Union):
            raise BindError("EXISTS over UNION unsupported")
        terms, inner_conjuncts, corr, corr_extra, nested, inner_glob = \
            self._split_correlation(q, glob)
        if not corr:
            raise BindError("uncorrelated EXISTS unsupported")
        if nested or corr_extra:
            raise BindError("complex correlation under OR'd EXISTS unsupported")
        saved = self._pending_subqueries
        self._pending_subqueries = []
        inner_node, _, inner_map = self._join_terms(terms, inner_conjuncts)
        self._pending_subqueries = saved
        left_keys = [
            remap_expr(ColumnRef(type=glob.cols[g].channel.type, index=g), remap)
            for _, g in corr
        ]
        right_keys = [remap_expr(ir, inner_map) for ir, _ in corr]
        mark_idx = len(node.channels)
        join = JoinNode(left=node, right=inner_node,
                        left_keys=left_keys, right_keys=right_keys, kind="mark")
        return join, mark_idx

    def _plan_in_mark(self, node, remap, glob, m):
        """value IN (subquery) as a mark join (uncorrelated only).
        The mark is three-valued (HashSemiJoinOperator.java:32): NULL
        when unmatched with a NULL probe value or a NULL on the
        subquery side, so negated uses under OR agree with ANSI IN."""
        sub, _ = self._plan_query_like(m.query)
        value_ir = remap_expr(self._bind(m.value, glob), remap)
        mark_idx = len(node.channels)
        join = JoinNode(
            left=node, right=sub, left_keys=[value_ir],
            right_keys=[ColumnRef(type=sub.channels[0].type, index=0)],
            kind="mark",
            null_aware=True,
        )
        return join, mark_idx

    def _is_correlated(self, q: ast.Query, outer_glob: Scope) -> bool:
        """A subquery is correlated iff it does not bind standalone."""
        try:
            self._plan_query_like(q)
            return False
        except BindError:
            return True

    def _split_correlation(self, q: ast.Query, outer_glob: Scope):
        """Plan a subquery's FROM; bind its WHERE in (inner + outer)
        scope; separate correlation equi-conjuncts from inner filters."""
        terms, conjuncts = self._flatten_from(q.from_)
        conjuncts = conjuncts + split_conjuncts(q.where)
        # correlation may hide inside an OR whose branches all repeat it
        conjuncts = [x for c in conjuncts for x in _extract_common_or_ast(c)]
        inner_glob = Scope([])
        for t in terms:
            inner_glob = inner_glob.concat(t.scope)

        combined = Scope(inner_glob.cols, parent=outer_glob)

        inner_conjuncts: List[ast.Node] = []
        corr: List[Tuple[Expr, int]] = []  # (inner ir, outer glob ref)
        # non-equi correlation: (cmp fn, inner ir, outer glob ref) —
        # decorrelated via per-group min/max aggregates (Q21 shape)
        corr_extra: List[Tuple[str, Expr, int]] = []
        nested: List[ast.Node] = []
        flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "ne": "ne"}
        for c in conjuncts:
            if _is_subquery_conjunct(c):
                nested.append(c)
                continue
            ir = self._bind(c, combined)
            refs = expr_refs(ir)
            outer_refs = [r for r in refs if r >= len(inner_glob)]
            if not outer_refs:
                inner_conjuncts.append(c)
            elif (
                isinstance(ir, Call) and ir.fn == "eq"
                and all(isinstance(a, ColumnRef) for a in ir.args)
                and len(outer_refs) == 1
            ):
                a, b = ir.args
                if a.index >= len(inner_glob):
                    a, b = b, a
                corr.append((a, b.index - len(inner_glob)))
            elif (
                isinstance(ir, Call) and ir.fn in flip
                and len(ir.args) == 2 and len(outer_refs) == 1
            ):
                a, b = ir.args
                fn = ir.fn
                if isinstance(a, ColumnRef) and a.index >= len(inner_glob):
                    a, b, fn = b, a, flip[fn]
                if not (
                    isinstance(b, ColumnRef) and b.index >= len(inner_glob)
                    and all(r < len(inner_glob) for r in expr_refs(a))
                ):
                    raise BindError(f"unsupported correlated predicate {c!r}")
                corr_extra.append((fn, a, b.index - len(inner_glob)))
            else:
                raise BindError(f"unsupported correlated predicate {c!r}")
        return terms, inner_conjuncts, corr, corr_extra, nested, inner_glob

    def _plan_exists(self, node, scope, remap, glob, q, kind: str):
        if isinstance(q, ast.Union):
            raise BindError("EXISTS over UNION unsupported")
        terms, inner_conjuncts, corr, corr_extra, nested, inner_glob = \
            self._split_correlation(q, glob)
        if not corr:
            raise BindError("uncorrelated EXISTS unsupported")
        if nested:
            raise BindError("nested subquery in EXISTS unsupported")
        saved = self._pending_subqueries
        self._pending_subqueries = []
        inner_node, _, inner_map = self._join_terms(terms, inner_conjuncts)
        self._pending_subqueries = saved

        left_keys = [
            remap_expr(ColumnRef(type=glob.cols[g].channel.type, index=g), remap)
            for _, g in corr
        ]
        right_keys = [remap_expr(ir, inner_map) for ir, _ in corr]

        if not corr_extra:
            join = JoinNode(
                left=node, right=inner_node, left_keys=left_keys, right_keys=right_keys,
                kind=kind,
            )
            return join, scope

        # Non-equi correlation (e.g. Q21's  l2.x <> l1.x):
        # EXISTS(k = outer.k AND x <> outer.x)  <=>
        #   group inner by k with min(x), max(x); left-join on k;
        #   matched AND (min <> outer.x OR max <> outer.x).
        # (for <,<=: test min; for >,>=: test max)
        if len(corr_extra) != 1:
            raise BindError("multiple non-equi correlated predicates unsupported")
        fn, inner_x, outer_g = corr_extra[0]
        x = remap_expr(inner_x, inner_map)
        group_irs = right_keys
        aggs = [AggCall("min", x, x.type), AggCall("max", x, x.type)]
        inner_scope_cols = [
            inner_glob.cols[g] for g, _ in sorted(inner_map.items(), key=lambda kv: kv[1])
        ]
        agg = AggregationNode(
            inner_node, group_irs, [f"$k{i}" for i in range(len(group_irs))],
            aggs, ["$min", "$max"],
            max_groups=self._group_capacity(
                group_irs, Scope(inner_scope_cols), self._estimate(inner_node)
            ),
        )
        key_refs = [ColumnRef(type=g.type, index=i) for i, g in enumerate(group_irs)]
        join = JoinNode(
            left=node, right=agg, left_keys=left_keys, right_keys=key_refs,
            kind="left", unique_build=True,
        )
        base = len(node.channels) + len(group_irs)
        min_ref = ColumnRef(type=x.type, index=base)
        max_ref = ColumnRef(type=x.type, index=base + 1)
        outer_val = remap_expr(
            ColumnRef(type=glob.cols[outer_g].channel.type, index=outer_g), remap
        )
        matched = call("not_null", min_ref)
        if fn == "ne":
            cond = call("and", matched,
                        call("or", call("ne", min_ref, outer_val), call("ne", max_ref, outer_val)))
        elif fn in ("lt", "le"):
            cond = call("and", matched, call(fn, min_ref, outer_val))
        elif fn in ("gt", "ge"):
            cond = call("and", matched, call(fn, max_ref, outer_val))
        else:
            raise BindError(f"unsupported correlated comparison {fn}")
        pred = cond if kind == "semi" else call("not", cond)
        return FilterNode(join, pred), scope

    def _plan_scalar_subquery(self, node, scope, remap, glob, q):
        """Returns (new node, scope, ColumnRef to the scalar value)."""
        if isinstance(q, ast.Union):
            sub_node, _ = self._plan_union(q)
            out = CrossSingleNode(left=node, right=sub_node)
            ref = ColumnRef(type=sub_node.channels[0].type, index=len(node.channels))
            return out, scope, ref
        if len(q.select) != 1:
            raise BindError("scalar subquery must select one column")
        sel = q.select[0].expr

        if not self._is_correlated(q, glob):
            # uncorrelated: plan the full query, single-row cross join
            sub_node, _ = self._plan_query_like(q)
            out = CrossSingleNode(left=node, right=sub_node)
            ref = ColumnRef(type=sub_node.channels[0].type, index=len(node.channels))
            return out, scope, ref

        terms, inner_conjuncts, corr, corr_extra, nested, inner_glob = \
            self._split_correlation(q, glob)
        if corr_extra:
            raise BindError("non-equi correlation in scalar subquery unsupported")
        if not corr:
            raise BindError(f"cannot bind scalar subquery {q!r}")
        saved = self._pending_subqueries
        self._pending_subqueries = []
        inner_node, _, inner_map = self._join_terms(terms, inner_conjuncts)
        pend = self._pending_subqueries
        self._pending_subqueries = saved
        inner_scope = Scope(
            [inner_glob.cols[g] for g, _ in sorted(inner_map.items(), key=lambda kv: kv[1])]
        )
        for c, cglob in pend:
            inner_node, inner_scope = self._apply_subquery_conjunct(
                inner_node, inner_scope, inner_map, c, cglob
            )

        # correlated scalar aggregate -> grouped agg joined on correlation
        if not self._contains_agg(sel):
            raise BindError("correlated scalar subquery must aggregate")
        group_irs = [remap_expr(ir, inner_map) for ir, _ in corr]
        agg_ctx = AggCtx(group_asts=[], group_irs=group_irs)
        sel_ir = self._bind_agg_scope(sel, inner_scope, inner_map, agg_ctx)
        agg = AggregationNode(
            inner_node, group_irs, [f"$k{i}" for i in range(len(group_irs))],
            agg_ctx.aggs, [f"$agg{j}" for j in range(len(agg_ctx.aggs))],
            max_groups=self._group_capacity(group_irs, inner_scope, self._estimate(inner_node)),
        )
        key_refs = [ColumnRef(type=g.type, index=i) for i, g in enumerate(group_irs)]
        proj = ProjectNode(agg, key_refs + [sel_ir],
                           [f"$k{i}" for i in range(len(key_refs))] + ["$scalar"])
        left_keys = [
            remap_expr(ColumnRef(type=glob.cols[g].channel.type, index=g), remap)
            for _, g in corr
        ]
        join = JoinNode(
            left=node, right=proj, left_keys=left_keys, right_keys=key_refs,
            kind="inner", unique_build=True,
        )
        ref = ColumnRef(type=sel_ir.type, index=len(node.channels) + len(key_refs))
        return join, scope, ref

    def _bind_agg_scope(self, e: ast.Node, inner_scope: Scope, inner_map, agg_ctx: AggCtx):
        """Bind a subquery select expr with aggregates over the joined
        inner tree (inner_scope indexes = tree channels)."""
        return self._bind_agg(e, inner_scope, agg_ctx)

    # ==================================================================
    # expression binding
    # ==================================================================
    def _bind(self, e: ast.Node, scope: Scope) -> Expr:
        return self._bind_impl(e, scope, None)

    def _bind_agg(self, e: ast.Node, scope: Scope, agg_ctx: AggCtx) -> Expr:
        return self._bind_impl(e, scope, agg_ctx)

    def _bind_impl(self, e: ast.Node, scope: Scope, agg: Optional[AggCtx]) -> Expr:
        try:
            return self._bind_node(e, scope, agg)
        except BindError as err:
            # attach the nearest enclosing node's statement offset; the
            # innermost failing node wins (recursion attaches first)
            if getattr(err, "pos", None) is None \
                    and getattr(e, "pos", None) is not None:
                err.pos = e.pos
            raise

    def _bind_node(self, e: ast.Node, scope: Scope, agg: Optional[AggCtx]) -> Expr:
        if agg is not None:
            # group-expr match (AST or bound-IR equality)
            for i, g in enumerate(agg.group_asts):
                if e == g:
                    return agg.key_ref(i)
            if not isinstance(e, (ast.NumberLit, ast.StringLit, ast.DateLit, ast.NullLit, ast.IntervalLit, ast.WindowExpr)):
                try:
                    ir = self._bind_impl(e, scope, None)
                    for i, g in enumerate(agg.group_irs):
                        if ir == g:
                            return agg.key_ref(i)
                except BindError:
                    pass
            if isinstance(e, ast.FuncCall) and e.name in AGG_FUNCTIONS:
                return self._bind_agg_call(e, scope, agg)
            if isinstance(e, ast.FuncCall) and e.name == "grouping":
                return self._bind_grouping(e, scope, agg)

        if isinstance(e, ast.Identifier) and e.qualifier is None:
            for frame in reversed(self._lambda_params):
                if e.name in frame:
                    return frame[e.name]

        if isinstance(e, ast.Identifier) and e.qualifier is None \
                and e.name.lower() in ("current_date", "current_timestamp",
                                       "localtimestamp"):
            # parenless niladic datetime functions (SqlBase.g4 specialForm);
            # bind-time constants so a query sees one consistent instant
            now = self._query_now()
            if e.name.lower() == "current_date":
                return Literal(type=DATE, value=int(now // 86400))
            return Literal(type=TIMESTAMP, value=int(now * 1_000_000))

        if isinstance(e, ast.Identifier) and e.qualifier is None \
                and e.name.lower() == "current_user":
            # SqlBase.g4 specialForm CURRENT_USER -> the session user
            return Literal(type=VARCHAR, value=self.session_user())

        if isinstance(e, ast.Identifier):
            try:
                idx = scope.resolve(e.qualifier, e.name)
            except BindError:
                # r.x / t.r.x where a prefix is a ROW-typed column:
                # progressively re-resolve the prefix as a column (bare
                # or table-qualified) and walk the rest as row fields
                # (DereferenceExpression's row branch)
                if e.qualifier is None:
                    raise
                parts = e.parts
                prefixes = [(parts[:1], parts[1:])]
                if len(parts) >= 3:
                    prefixes.append((parts[:2], parts[2:]))
                for head, fields in prefixes:
                    try:
                        base = self._bind_impl(
                            ast.Identifier(tuple(head)), scope, agg)
                    except BindError:
                        continue
                    for f in fields:
                        base = self._row_field(base, f)
                    return base
                raise
            ch = scope.col(idx).channel
            if agg is not None:
                raise BindError(f"column {e.name} not in GROUP BY")
            return ColumnRef(type=ch.type, index=idx, name=e.name)

        if isinstance(e, ast.QuantifiedComparison):
            return self._bind_impl(desugar_quantified(e), scope, agg)

        if isinstance(e, ast.FieldAccess):
            return self._row_field(self._bind_impl(e.base, scope, agg),
                                   e.field)

        if isinstance(e, ast.ScalarSubquery):
            ref = self._scalar_refs.get(id(e))
            if ref is not None:
                return ref

        if isinstance(e, (ast.Exists, ast.InSubquery)):
            # lowered to a mark-join boolean column by
            # _apply_subquery_conjunct's general fallback
            ref = self._mark_refs.get(id(e))
            if ref is not None:
                return call("not", ref) if e.negated else ref

        if isinstance(e, ast.NumberLit):
            return self._bind_number(e.text)
        if isinstance(e, ast.StringLit):
            return Literal(type=VARCHAR, value=e.value)
        if isinstance(e, ast.DateLit):
            return Literal(type=DATE, value=_parse_date(e.value))
        if isinstance(e, ast.TimestampLit):
            return Literal(type=TIMESTAMP, value=_parse_timestamp(e.value))
        if isinstance(e, ast.TimeLit):
            from presto_tpu.types import TIME as _TIME

            return Literal(type=_TIME, value=_parse_time_of_day(e.value))
        if isinstance(e, ast.NullLit):
            return Literal(type=BIGINT, value=None)

        if isinstance(e, ast.IntervalLit):
            # standalone interval VALUE (spi IntervalDayTimeType /
            # IntervalYearMonthType): micros / months on device
            t, v = _interval_literal(e)
            return Literal(type=t, value=v)

        if isinstance(e, ast.Parameter):
            raise BindError(
                f"unbound parameter ?{e.index + 1} — run via EXECUTE ... USING")

        if isinstance(e, ast.Binary):
            if e.op in ("and", "or"):
                return call(e.op, self._bind_impl(e.left, scope, agg), self._bind_impl(e.right, scope, agg))
            if e.op in ("=", "<>") and (
                _is_row_ast(e.left) or _is_row_ast(e.right)
            ):
                return self._bind_impl(
                    _row_comparison(e.left, e.right, e.op), scope, agg)
            if e.op in ("=", "<>", "<", "<=", ">", ">="):
                opmap = {"=": "eq", "<>": "ne", "<": "lt", "<=": "le", ">": "gt", ">=": "ge"}
                l_ir = self._bind_impl(e.left, scope, agg)
                r_ir = self._bind_impl(e.right, scope, agg)
                if l_ir.type.name == "row" or r_ir.type.name == "row":
                    raise BindError(
                        "ROW comparisons desugar pairwise — compare "
                        "row constructors directly, not row-typed "
                        "values")
                return call(opmap[e.op], l_ir, r_ir)
            if e.op in ("+", "-") and (
                isinstance(e.right, ast.IntervalLit)
                or isinstance(e.left, ast.IntervalLit)
            ) and not (isinstance(e.right, ast.IntervalLit)
                       and isinstance(e.left, ast.IntervalLit)):
                # literal-interval date arithmetic keeps the civil
                # month/year shift semantics for DATE bases — but only
                # when the OTHER side is not itself an interval
                probe = self._bind_impl(
                    e.left if isinstance(e.right, ast.IntervalLit)
                    else e.right, scope, agg)
                if not probe.type.name.startswith("interval"):
                    return self._bind_date_arith(e, scope, agg)
            opmap = {"+": "add", "-": "sub", "*": "mul", "/": "div", "%": "mod"}
            l_ir = self._bind_impl(e.left, scope, agg)
            r_ir = self._bind_impl(e.right, scope, agg)
            iv_arith = self._bind_interval_arith(e.op, l_ir, r_ir)
            if iv_arith is not None:
                return iv_arith
            if e.op == "-" and l_ir.type.name == r_ir.type.name \
                    and l_ir.type.name in ("timestamp", "date"):
                # datetime difference -> INTERVAL DAY TO SECOND
                # (IntervalDayTimeType; micros on device)
                from presto_tpu.types import INTERVAL_DAY_SECOND

                if l_ir.type.name == "date":
                    l_ir = call("cast_bigint", l_ir)
                    r_ir = call("cast_bigint", r_ir)
                    days = Call(type=BIGINT, fn="sub", args=(l_ir, r_ir))
                    return Call(
                        type=INTERVAL_DAY_SECOND, fn="mul",
                        args=(days,
                              Literal(type=BIGINT, value=MICROS_PER_DAY)))
                return Call(type=INTERVAL_DAY_SECOND, fn="sub",
                            args=(l_ir, r_ir))
            return call(opmap[e.op], l_ir, r_ir)

        if isinstance(e, ast.Unary):
            if e.op == "not":
                return call("not", self._bind_impl(e.operand, scope, agg))
            operand = self._bind_impl(e.operand, scope, agg)
            if isinstance(operand, Literal) and operand.value is not None:
                return Literal(type=operand.type, value=-operand.value)
            return call("neg", operand)

        if isinstance(e, ast.Between):
            v = self._bind_impl(e.value, scope, agg)
            lo = self._bind_impl(e.low, scope, agg)
            hi = self._bind_impl(e.high, scope, agg)
            out = call("between", v, lo, hi)
            return call("not", out) if e.negated else out

        if isinstance(e, ast.InList):
            if _is_row_ast(e.value):
                # (a, b) IN ((1, 2), (3, 4)) -> OR of pairwise ANDs
                # (sql/tree/Row.java comparisons; row(a, b) form too)
                out_ast = None
                for item in e.items:
                    conj = _row_comparison(e.value, item, "=")
                    out_ast = conj if out_ast is None else ast.Binary("or", out_ast, conj)
                if out_ast is None:
                    raise BindError("empty IN list")
                if e.negated:
                    out_ast = ast.Unary("not", out_ast)
                return self._bind_impl(out_ast, scope, agg)
            v = self._bind_impl(e.value, scope, agg)
            items = [self._bind_impl(x, scope, agg) for x in e.items]
            out = call("in", v, *items)
            return call("not", out) if e.negated else out

        if isinstance(e, ast.Like):
            v = self._bind_impl(e.value, scope, agg)
            p = self._bind_impl(e.pattern, scope, agg)
            out = call("like", v, p)
            return call("not", out) if e.negated else out

        if isinstance(e, ast.IsNull):
            v = self._bind_impl(e.value, scope, agg)
            return call("is_null" if not e.negated else "not_null", v)

        if isinstance(e, ast.WindowExpr):
            return self._register_window(e, scope, agg)

        if isinstance(e, ast.Case):
            return self._bind_case(e, scope, agg)

        if isinstance(e, ast.Cast):
            v = self._bind_impl(e.value, scope, agg)
            tn = e.type_name.lower()
            if isinstance(v, Literal) and v.type == VARCHAR \
                    and tn in ("double", "double precision", "bigint",
                               "integer", "int"):
                # unparseable / out-of-int64-range -> NULL (deviation:
                # the reference raises; the column form's dictionary
                # LUT uses the same strict parser)
                from presto_tpu.expr.compile import parse_number_strict

                return Literal(
                    type=DOUBLE if tn.startswith("double") else BIGINT,
                    value=parse_number_strict(
                        v.value, tn.startswith("double")))
            if tn in ("double", "double precision"):
                return call("cast_double", v)
            if tn in ("bigint", "integer", "int"):
                return call("cast_bigint", v)
            if tn == "date":
                if isinstance(v, Literal) and v.type == VARCHAR:
                    return Literal(type=DATE, value=_parse_date(v.value))
                return call("cast_date", v)
            if tn == "timestamp":
                if isinstance(v, Literal) and v.type == VARCHAR:
                    return Literal(type=TIMESTAMP, value=_parse_timestamp(v.value))
                return call("cast_timestamp", v)
            if tn.startswith("decimal"):
                from presto_tpu.types import parse_type

                t = parse_type(tn)
                if v.type.is_decimal and v.type.scale == t.scale \
                        and v.type.is_long_decimal == t.is_long_decimal \
                        and v.type.value_shape == t.value_shape:
                    return v
                return call("cast_decimal", v,
                            Literal(type=BIGINT, value=t.precision or 18),
                            Literal(type=BIGINT, value=t.scale or 0))
            if tn == "real":
                return call("cast_real", v)
            if tn == "smallint":
                return call("cast_smallint", v)
            if tn == "tinyint":
                return call("cast_tinyint", v)
            if tn == "time":
                from presto_tpu.types import TIME as _TIME

                if isinstance(v, Literal) and v.type == VARCHAR:
                    return Literal(type=_TIME,
                                   value=_parse_time_of_day(v.value))
                return call("cast_time", v)
            if tn.startswith("char"):
                if v.type.is_string and not v.type.is_raw_string:
                    from presto_tpu.types import parse_type

                    return call("cast_char", v,
                                Literal(type=BIGINT,
                                        value=parse_type(tn).precision or 32))
            if tn.startswith("varbinary"):
                if v.type.is_raw_string:
                    from presto_tpu.types import parse_type

                    # a raw varchar IS a byte matrix; re-type in place
                    return call("cast_varbinary", v,
                                Literal(type=BIGINT,
                                        value=parse_type(tn).precision
                                        or (v.type.precision or 32)))
            if tn.startswith("varchar"):
                # identity for string-typed values (the engine's strings
                # are dictionary codes; re-typing is metadata-only)
                if v.type.is_string:
                    return v
            if tn.startswith("row"):
                from presto_tpu.types import parse_type

                target = parse_type(tn)
                if v.type.name != "row":
                    raise BindError("CAST to ROW requires a row value")
                if len(v.type.fields) != len(target.fields):
                    raise BindError("ROW cast arity mismatch")
                if tuple(v.type.fields) == tuple(target.fields):
                    # naming-only cast: the storage matrix is unchanged
                    return Call(type=target, fn="retype_row", args=(v,))
                # field types differ: rebuild the row from converted
                # fields (value conversion, e.g. decimal -> double)
                conv = {"double": "cast_double", "bigint": "cast_bigint",
                        "integer": "cast_bigint", "real": "cast_real"}
                new_fields = []
                for i, (st, dt) in enumerate(zip(v.type.fields,
                                                 target.fields)):
                    f = Call(type=st, fn="row_field",
                             args=(v, Literal(type=BIGINT, value=i + 1)))
                    if st != dt:
                        if dt.name not in conv:
                            raise BindError(
                                f"ROW cast cannot convert {st} to {dt}")
                        f = call(conv[dt.name], f)
                    new_fields.append(f)
                return Call(type=target, fn="row_construct",
                            args=tuple(new_fields))
            raise BindError(f"unsupported CAST to {e.type_name}")

        if isinstance(e, ast.Extract):
            field = {"dow": "day_of_week", "doy": "day_of_year"}.get(e.field, e.field)
            return call(field, self._bind_impl(e.value, scope, agg))

        if isinstance(e, ast.Lambda):
            raise BindError("lambda only valid as an argument of "
                            "transform/filter/any_match/all_match/none_match")

        if isinstance(e, ast.FuncCall):
            if e.ignore_nulls:
                # only the window value functions under OVER consume it
                raise BindError(
                    "IGNORE NULLS applies to window value functions "
                    "(lead/lag/first_value/last_value/nth_value OVER)")
            if e.name in ("transform", "filter", "any_match", "all_match",
                          "none_match") and len(e.args) == 2 \
                    and isinstance(e.args[1], ast.Lambda):
                return self._bind_array_lambda(e, scope, agg)
            if (e.name in ("map_filter", "transform_keys",
                           "transform_values") and len(e.args) == 2) \
                    or (e.name == "zip_with" and len(e.args) == 3) \
                    or (e.name == "reduce" and len(e.args) == 4):
                return self._bind_container_lambda(e, scope, agg)
            if e.name == "row" and e.args:
                # first-class anonymous ROW value (spi/type/RowType.java
                # subset: fixed-width scalar fields, 1-based subscript)
                from presto_tpu.types import RowType

                items = [self._bind_impl(a, scope, agg) for a in e.args]
                try:
                    rt = RowType(*[a.type for a in items])
                except ValueError as ex:
                    raise BindError(str(ex))
                return Call(type=rt, fn="row_construct", args=tuple(items))
            if e.name == "split":
                if len(e.args) not in (2, 3):
                    raise BindError("split takes (string, delimiter"
                                    "[, limit])")
                dl = self._bind_impl(e.args[1], scope, agg)
                if not isinstance(dl, Literal) or not dl.value:
                    raise BindError(
                        "split delimiter must be a non-empty literal")
                if len(e.args) == 3:
                    lim = self._bind_impl(e.args[2], scope, agg)
                    if not isinstance(lim, Literal) or lim.value is None \
                            or not lim.type.is_integerlike \
                            or not 1 <= int(lim.value) <= 64:
                        raise BindError(
                            "split limit must be a literal in [1, 64]")
            if e.name == "map_concat" and len(e.args) > 2:
                # variadic: left-fold into binary concats
                folded = ast.FuncCall("map_concat", e.args[:2])
                for extra in e.args[2:]:
                    folded = ast.FuncCall("map_concat", (folded, extra))
                return self._bind_impl(folded, scope, agg)
            if e.name == "typeof":
                if len(e.args) != 1:
                    raise BindError("typeof takes one argument")
                arg = self._bind_impl(e.args[0], scope, agg)
                return Literal(type=VARCHAR, value=repr(arg.type))
            if e.name == "now":
                if e.args:
                    raise BindError("now() takes no arguments")
                return Literal(type=TIMESTAMP,
                               value=int(self._query_now() * 1_000_000))
            if e.name in ("pi", "e", "nan", "infinity") and not e.args:
                import math as _math

                return Literal(type=DOUBLE, value={
                    "pi": _math.pi, "e": _math.e, "nan": _math.nan,
                    "infinity": _math.inf}[e.name])
            if e.name == "to_iso8601" and len(e.args) == 1:
                # date -> ISO 'yyyy-mm-dd' via the date_format domain
                # dictionary (DateTimeFunctions.java#toISO8601);
                # timestamps would silently lose time-of-day, so reject
                arg0 = self._bind_impl(e.args[0], scope, agg)
                if arg0.type.name != "date":
                    raise BindError(
                        "to_iso8601 supports DATE arguments (a "
                        "timestamp's time-of-day has no domain "
                        "dictionary)")
                return self._bind_impl(
                    ast.FuncCall("date_format",
                                 (e.args[0], ast.StringLit("%Y-%m-%d"))),
                    scope, agg)
            if e.name in ("day_name", "month_name") and len(e.args) == 1:
                fmt = "%W" if e.name == "day_name" else "%M"
                return self._bind_impl(
                    ast.FuncCall("date_format",
                                 (e.args[0], ast.StringLit(fmt))),
                    scope, agg)
            if e.name == "format_datetime" and len(e.args) == 2:
                # Joda pattern subset -> the MySQL codes date_format
                # speaks (DateTimeFunctions.java#formatDatetime)
                p = self._bind_impl(e.args[1], scope, agg)
                if not isinstance(p, Literal) or p.value is None:
                    raise BindError(
                        "format_datetime pattern must be a literal")
                return self._bind_impl(
                    ast.FuncCall(
                        "date_format",
                        (e.args[0], ast.StringLit(_joda_to_mysql(p.value)))),
                    scope, agg)
            if e.name == "concat_ws" and len(e.args) >= 2:
                # separator-joined concat (deviation: a NULL argument
                # nulls the result; the reference skips NULLs)
                sep = e.args[0]
                parts: list = []
                for i, a in enumerate(e.args[1:]):
                    if i:
                        parts.append(sep)
                    parts.append(a)
                return self._bind_impl(
                    ast.FuncCall("concat", tuple(parts)), scope, agg)
            if e.name == "to_hex" and len(e.args) == 1 \
                    and isinstance(e.args[0], ast.FuncCall) \
                    and e.args[0].name in ("md5", "sha1", "sha256") \
                    and len(e.args[0].args) == 1 \
                    and isinstance(e.args[0].args[0], ast.FuncCall) \
                    and e.args[0].args[0].name == "to_utf8":
                # to_hex(md5(to_utf8(x))) collapses into one dictionary
                # transform (VarbinaryFunctions md5/sha*/toHexString)
                inner = e.args[0].args[0].args[0]
                return self._bind_impl(
                    ast.FuncCall(f"{e.args[0].name}_hex", (inner,)),
                    scope, agg)
            if e.name in ("week_of_year", "yow", "doy", "dow",
                          "day_of_month"):
                # DateTimeFunctions.java aliases
                canon = {"week_of_year": "week", "yow": "year_of_week",
                         "doy": "day_of_year", "dow": "day_of_week",
                         "day_of_month": "day"}[e.name]
                return self._bind_impl(
                    ast.FuncCall(canon, e.args), scope, agg)
            if e.name == "chr":
                # code point -> single-char string; literal-foldable
                # only (a column form would need a dynamic dictionary)
                arg = self._bind_impl(e.args[0], scope, agg) if e.args else None
                if not isinstance(arg, Literal):
                    raise BindError("chr requires an integer literal")
                if arg.value is None:
                    return Literal(type=VARCHAR, value=None)
                cp = int(arg.value)
                if not 0 <= cp < 0x110000:
                    raise BindError(f"chr code point out of range: {cp}")
                return Literal(type=VARCHAR, value=chr(cp))
            if e.name == "to_base":
                if len(e.args) != 2:
                    raise BindError("to_base takes (value, radix)")
                v = self._bind_impl(e.args[0], scope, agg)
                rx = self._bind_impl(e.args[1], scope, agg)
                if not isinstance(v, Literal) or not isinstance(rx, Literal):
                    raise BindError(
                        "to_base supports literal arguments only (a "
                        "column form would need a dynamic dictionary)")
                if v.value is None or rx.value is None:
                    return Literal(type=VARCHAR, value=None)
                n, radix = int(v.value), int(rx.value)
                if not 2 <= radix <= 36:
                    raise BindError("to_base radix must be in [2, 36]")
                digits = "0123456789abcdefghijklmnopqrstuvwxyz"
                m, out = abs(n), ""
                while True:
                    m, r = divmod(m, radix)
                    out = digits[r] + out
                    if m == 0:
                        break
                return Literal(type=VARCHAR,
                               value=("-" if n < 0 else "") + out)
            if e.name == "index":
                # teradata index(s, sub) = strpos (DateTimeFunctions.java
                # analog in presto-teradata-functions)
                return self._bind_impl(
                    ast.FuncCall("strpos", e.args), scope, agg)
            if e.name == "nvl":
                return self._bind_impl(
                    ast.FuncCall("coalesce", e.args), scope, agg)
            if e.name == "try":
                # TRY(e): the trappable errors the reference's
                # TryExpression catches (division by zero, unparseable
                # casts, out-of-range subscripts) already evaluate to
                # NULL engine-wide (XLA kernels cannot trap), so TRY
                # compiles to the identity (sql/tree/TryExpression.java
                # + DesugarTryExpression.java) — but the marker stays
                # in the IR so the kernel-soundness tier knows hazards
                # beneath it are sanctioned: inside TRY the reference
                # ALSO returns NULL, so NULLed lanes are not deviations
                if len(e.args) != 1:
                    raise BindError("try takes one argument")
                inner = self._bind_impl(e.args[0], scope, agg)
                return Call(type=inner.type, fn="try", args=(inner,))
            if e.name == "features":
                # presto-ml feature vector -> ARRAY(double)
                args = [call("cast_double", self._bind_impl(a, scope, agg))
                        for a in e.args]
                return call("array_construct", *args)
            if e.name in AGG_FUNCTIONS:
                if agg is None:
                    raise BindError(f"aggregate {e.name} in scalar context")
                return self._bind_agg_call(e, scope, agg)
            if e.name in SCALAR_FUNCTIONS:
                _arity = {"array_intersect": 2, "array_union": 2,
                          "array_except": 2, "arrays_overlap": 2,
                          "array_remove": 2}.get(e.name)
                if _arity is not None and len(e.args) != _arity:
                    raise BindError(
                        f"{e.name} takes {_arity} arguments")
                if e.name == "map_concat" and len(e.args) < 2:
                    raise BindError("map_concat takes at least two maps")
                args = [self._bind_impl(a, scope, agg) for a in e.args]
                folded = self._fold_literal_call(e.name, args)
                if folded is not None:
                    return folded
                if e.name == "concat" and len(args) == 2 \
                        and any(a.type.is_array for a in args):
                    # ARRAY || scalar appends the element (and the
                    # symmetric prepend) — wrap the scalar side
                    a0, a1 = args
                    if any((a.type.is_array and a.type.element is not None
                            and a.type.element.is_string)
                           or (not a.type.is_array and a.type.is_string)
                           for a in args):
                        # literal string arrays each carry their OWN
                        # derived dictionary; concatenation would mix
                        # incompatible code spaces (silent NULLs)
                        raise BindError(
                            "string-array concatenation unsupported")
                    if not a0.type.is_array:
                        a0 = call("array_construct", a0)
                    if not a1.type.is_array:
                        a1 = call("array_construct", a1)
                    return call("array_concat", a0, a1)
                if e.name == "concat":
                    if any(isinstance(a, Literal) and a.value is None for a in args):
                        return Literal(type=VARCHAR, value=None)  # NULL-propagating
                    non_lit = [a for a in args if not isinstance(a, Literal)]
                    if not non_lit:
                        return Literal(type=VARCHAR,
                                       value="".join(str(a.value) for a in args))
                    if (len(non_lit) != 1
                            and not all(a.type.is_raw_string for a in non_lit)):
                        raise BindError(
                            "multi-column concat needs raw varchar operands"
                            " (dictionary columns support one column + literals)")
                return call(e.name, *args)
            raise BindError(f"unknown function {e.name}",
                            pos=getattr(e, "pos", None))

        if isinstance(e, ast.ArrayCtor):
            items = [self._bind_impl(x, scope, agg) for x in e.items]
            if not items:
                raise BindError("empty ARRAY[] needs a typed context")
            # NULL literals adopt the elements' common type
            typed = [a for a in items if not (isinstance(a, Literal) and a.value is None)]
            if typed:
                elem_t = typed[0].type
                for a in typed[1:]:
                    elem_t = common_super_type(elem_t, a.type)
                items = [
                    Literal(type=elem_t, value=None)
                    if isinstance(a, Literal) and a.value is None else a
                    for a in items
                ]
            if any(a.type.is_string for a in items):
                # all-literal string arrays ride a derived dictionary
                # (codes constructed at compile time; VERDICT r5's
                # UNNEST(MAP(..., ARRAY['a','b'])) probe needs them);
                # anything computed stays unsupported
                if not all(isinstance(a, Literal) for a in items):
                    raise BindError(
                        "ARRAY of strings unsupported in expressions (array "
                        "columns with dictionary-coded string elements work)")
            if any(a.type.is_array or a.type.is_map for a in items):
                # element types now UNIFY (identical widths no longer
                # error, VERDICT r5), but the flat container storage
                # has no nested-array value layout — report the real
                # limitation instead of leaking a storage ValueError
                raise BindError(
                    "nested ARRAY construction unsupported: array "
                    "elements must be fixed-width scalars")
            return call("array_construct", *items)

        if isinstance(e, ast.Subscript):
            base = self._bind_impl(e.base, scope, agg)
            idx = self._bind_impl(e.index, scope, agg)
            if base.type.name == "row":
                if not isinstance(idx, Literal) or idx.value is None:
                    raise BindError("ROW field index must be a literal")
                i = int(idx.value)
                if not 1 <= i <= len(base.type.fields):
                    raise BindError(
                        f"ROW field index {i} out of range "
                        f"[1, {len(base.type.fields)}]")
                return Call(type=base.type.fields[i - 1], fn="row_field",
                            args=(base, Literal(type=BIGINT, value=i)))
            return call("subscript", base, idx)

        if isinstance(e, ast.Substring):
            v = self._bind_impl(e.value, scope, agg)
            start = self._bind_impl(e.start, scope, agg)
            if not isinstance(start, Literal):
                raise BindError("substring start must be a literal")
            args = [v, start]
            if e.length is not None:
                ln = self._bind_impl(e.length, scope, agg)
                if not isinstance(ln, Literal):
                    raise BindError("substring length must be a literal")
                args.append(ln)
            folded = self._fold_literal_call("substr", args)
            if folded is not None:
                return folded
            return call("substr", *args)

        raise BindError(f"cannot bind {e!r}")

    def _bind_array_lambda(self, e: ast.FuncCall, scope: Scope, agg) -> Expr:
        """transform/filter/..._match(arr, x -> body): the lambda body
        binds in a scope where the parameter resolves to a LambdaVar of
        the array's element type (LambdaBytecodeGenerator's captured
        scope, realized as an extra virtual channel)."""
        from presto_tpu.expr.ir import LambdaVar

        arr = self._bind_impl(e.args[0], scope, agg)
        if not arr.type.is_array:
            raise BindError(f"{e.name} expects an ARRAY first argument")
        lam: ast.Lambda = e.args[1]
        var = LambdaVar(type=arr.type.element,
                        slot=next(self._lambda_slot_seq))
        body = self._bind_lambda_body(lam.body, {lam.param: var}, scope, agg)
        fn = {"transform": "array_transform", "filter": "array_filter"}.get(
            e.name, e.name)
        if fn == "array_filter" or fn.endswith("_match"):
            if body.type.name != "boolean":
                raise BindError(f"{e.name} lambda must return boolean")
        from presto_tpu.expr.ir import LambdaExpr

        return call(fn, arr, LambdaExpr(type=body.type, params=(var,),
                                        body=body))

    def _bind_lambda_body(self, body: ast.Node, params: dict,
                          scope: Scope, agg) -> Expr:
        """Bind with the lambda parameters shadowing outer columns (and
        exempt from group-key checks inside aggregate contexts): a
        scoped parameter frame is consulted before identifier
        resolution.  ``params`` maps name -> LambdaVar."""
        self._lambda_params.append(dict(params))
        try:
            return self._bind_impl(body, scope, agg)
        finally:
            self._lambda_params.pop()

    def _bind_container_lambda(self, e: ast.FuncCall, scope: Scope,
                               agg) -> Expr:
        """map_filter / transform_keys / transform_values / zip_with /
        reduce — the multi-parameter lambda surface
        (MapFilterFunction.java, MapTransformKeyFunction.java,
        MapTransformValueFunction.java, ZipWithFunction.java,
        ReduceFunction.java).  Lambda parameters become slot-numbered
        LambdaVars bound to flattened entry lanes by the compiler."""
        from presto_tpu.expr.ir import LambdaExpr, LambdaVar
        from presto_tpu.types import ArrayType, MapType

        name = e.name

        def lam_of(a, n_params):
            if not isinstance(a, ast.Lambda) or len(a.all_params) != n_params:
                raise BindError(
                    f"{name} expects a {n_params}-parameter lambda")
            return a

        def new_var(t):
            return LambdaVar(type=t, slot=next(self._lambda_slot_seq))

        if name in ("map_filter", "transform_keys", "transform_values"):
            m = self._bind_impl(e.args[0], scope, agg)
            if not m.type.is_map or m.type.name != "map" or (
                    m.type.element is not None and m.type.element.is_array):
                raise BindError(f"{name} expects a scalar-valued map")
            lam = lam_of(e.args[1], 2)
            kv, vv = new_var(m.type.key_element), new_var(m.type.element)
            body = self._bind_lambda_body(
                lam.body, {lam.all_params[0]: kv, lam.all_params[1]: vv},
                scope, agg)
            if name == "map_filter":
                if body.type.name != "boolean":
                    raise BindError("map_filter lambda must return boolean")
                out_t = m.type
            elif name == "transform_keys":
                out_t = MapType(body.type, m.type.element, m.type.max_elems)
            else:
                out_t = MapType(m.type.key_element, body.type,
                                m.type.max_elems)
            le = LambdaExpr(type=body.type, params=(kv, vv), body=body)
            return Call(type=out_t, fn=name, args=(m, le))
        if name == "zip_with":
            a1 = self._bind_impl(e.args[0], scope, agg)
            a2 = self._bind_impl(e.args[1], scope, agg)
            if not (a1.type.is_array and a2.type.is_array):
                raise BindError("zip_with expects two arrays")
            lam = lam_of(e.args[2], 2)
            xv, yv = new_var(a1.type.element), new_var(a2.type.element)
            body = self._bind_lambda_body(
                lam.body, {lam.all_params[0]: xv, lam.all_params[1]: yv},
                scope, agg)
            out_t = ArrayType(body.type,
                              max(a1.type.max_elems, a2.type.max_elems))
            le = LambdaExpr(type=body.type, params=(xv, yv), body=body)
            return Call(type=out_t, fn=name, args=(a1, a2, le))
        # reduce(arr, init, (s, x) -> comb, s -> out)
        arr = self._bind_impl(e.args[0], scope, agg)
        if not arr.type.is_array:
            raise BindError("reduce expects an array first argument")
        init = self._bind_impl(e.args[1], scope, agg)
        comb_l = lam_of(e.args[2], 2)
        sv = new_var(init.type)
        xv = new_var(arr.type.element)
        comb = self._bind_lambda_body(
            comb_l.body, {comb_l.all_params[0]: sv, comb_l.all_params[1]: xv},
            scope, agg)
        if comb.type != init.type:
            raise BindError(
                f"reduce combiner returns {comb.type}, state is {init.type}")
        out_l = lam_of(e.args[3], 1)
        sv2 = new_var(init.type)
        out_body = self._bind_lambda_body(
            out_l.body, {out_l.all_params[0]: sv2}, scope, agg)
        return Call(
            type=out_body.type, fn="reduce",
            args=(arr, init,
                  LambdaExpr(type=comb.type, params=(sv, xv), body=comb),
                  LambdaExpr(type=out_body.type, params=(sv2,),
                             body=out_body)))

    def _bind_grouping(self, e: ast.FuncCall, scope: Scope, agg: AggCtx) -> Expr:
        """grouping(a, b, ...) -> bitmask int: bit j (MSB-first) is 1
        when argument j is NOT aggregated in the current grouping set
        (sql/tree/GroupingOperation.java + the reference's rewrite to a
        $group_id lookup in QueryPlanner.planGroupingOperations)."""
        from presto_tpu.expr.ir import lit

        if agg.set_masks is None:
            raise BindError(
                "grouping() requires GROUPING SETS / ROLLUP / CUBE")
        idxs = []
        for a in e.args:
            hit = next((i for i, g in enumerate(agg.group_asts) if g == a), None)
            if hit is None:
                raise BindError(
                    f"grouping() argument {a!r} is not a grouping column")
            idxs.append(hit)
        k = len(idxs)
        vals = []
        for mask in agg.set_masks:
            v = 0
            for j, i in enumerate(idxs):
                if not mask[i]:
                    v |= 1 << (k - 1 - j)
            vals.append(v)
        gid_ref = agg.key_ref(len(agg.group_irs) - 1)  # $group_id key
        expr: Expr = lit(vals[-1], BIGINT)
        for g in range(len(vals) - 2, -1, -1):
            expr = call("if", call("eq", gid_ref, lit(g, BIGINT)),
                        lit(vals[g], BIGINT), expr)
        return expr

    def _bind_number(self, text: str) -> Literal:
        if "e" in text.lower():
            return Literal(type=DOUBLE, value=float(text))
        if "." in text:
            # exact digit parse (float round-trips lose precision past
            # 15-16 digits); > 18 digits becomes a long decimal
            whole, frac = text.split(".", 1)
            scale = len(frac)
            scaled = int((whole + frac) or "0")
            digits = len((whole + frac).lstrip("+-").lstrip("0")) or 1
            precision = max(digits, scale)
            if precision > 38:
                raise BindError(f"decimal literal exceeds 38 digits: {text}")
            if precision > 36:
                return Literal(type=DecimalType(38, scale), value=scaled)
            return Literal(type=DecimalType(36 if precision > 18 else 18, scale),
                           value=scaled)
        v = int(text)
        if not (-(1 << 63) <= v < (1 << 63)):
            # integer literal beyond int64: a decimal(<=38, 0) literal
            # (the reference types wide literals as decimals too)
            digits = len(text.lstrip("+-").lstrip("0")) or 1
            if digits > 38:
                raise BindError(f"decimal literal exceeds 38 digits: {text}")
            return Literal(type=DecimalType(38 if digits > 36 else 36, 0),
                           value=v)
        return Literal(type=BIGINT, value=v)

    def _bind_interval_arith(self, op: str, l_ir: Expr,
                             r_ir: Expr) -> Optional[Expr]:
        """Typed interval arithmetic (dispatch on BOUND types, so
        interval-valued sub-expressions work like literals):
        interval +- interval (same family), datetime +- day-second
        interval, datetime +- year-month interval.  Returns None when
        neither operand is interval-typed."""
        from presto_tpu.types import INTERVAL_DAY_SECOND

        IV = ("interval day to second", "interval year to month")
        lt, rt = l_ir.type.name, r_ir.type.name
        if lt not in IV and rt not in IV:
            return None
        if op in ("*", "/"):
            # interval scaled by a number (IntervalDayTimeOperators
            # multiplyBy*/dividedBy*: the product truncates to the unit
            # count like the reference's (long) cast)
            if op == "*" and lt not in IV and l_ir.type.is_numeric:
                iv, k = r_ir, l_ir
            elif rt not in IV and r_ir.type.is_numeric:
                iv, k = l_ir, r_ir
            else:
                raise BindError(
                    f"operator {op} undefined for these interval operands")
            from presto_tpu.types import DOUBLE as _DOUBLE

            exact = op == "*" and k.type.name in (
                "bigint", "integer", "smallint", "tinyint")
            if exact:
                return Call(type=iv.type, fn="mul", args=(iv, k))
            # fractional scale: compute in double, truncate like the
            # reference's (long) cast
            prod = Call(type=_DOUBLE, fn="mul" if op == "*" else "div",
                        args=(iv, k))
            return Call(type=iv.type, fn="cast_bigint", args=(prod,))
        if op not in ("+", "-"):
            raise BindError(f"operator {op} undefined for intervals")
        if lt in IV and rt in IV:
            if lt != rt:
                raise BindError(
                    "cannot mix day-second and year-month intervals")
            return Call(type=l_ir.type, fn="add" if op == "+" else "sub",
                        args=(l_ir, r_ir))
        iv, base = (l_ir, r_ir) if lt in IV else (r_ir, l_ir)
        if base.type.name not in ("timestamp", "date"):
            raise BindError(
                f"cannot apply interval to {base.type}")
        if op == "-" and lt in IV:
            raise BindError("interval - datetime unsupported")
        if op == "-":
            iv = Call(type=iv.type, fn="mul",
                      args=(iv, Literal(type=BIGINT, value=-1)))
        if iv.type == INTERVAL_DAY_SECOND:
            if base.type == DATE:
                base = call("cast_timestamp", base)
            return call("ts_add_micros", base, iv)
        if base.type == DATE:
            return call("date_add_months", base, iv)
        return call("ts_add_months", base, iv)

    def _bind_date_arith(self, e: ast.Binary, scope: Scope, agg) -> Expr:
        if isinstance(e.right, ast.IntervalLit):
            base_ast, iv = e.left, e.right
        else:
            if e.op == "-":
                raise BindError("interval - date unsupported")
            base_ast, iv = e.right, e.left
        # ONE literal parser serves the standalone-value and date-arith
        # paths (fractional seconds, 'Y-M', signed strings included)
        t_iv, v_iv = _interval_literal(iv)
        if e.op == "-":
            v_iv = -v_iv
        base = self._bind_impl(base_ast, scope, agg)
        from presto_tpu.types import INTERVAL_DAY_SECOND

        if t_iv == INTERVAL_DAY_SECOND:
            whole_days = v_iv % MICROS_PER_DAY == 0
            if isinstance(base, Literal) and base.type == DATE \
                    and base.value is not None:
                if whole_days:  # civil DATE shift stays a DATE
                    return Literal(type=DATE,
                                   value=base.value + v_iv // MICROS_PER_DAY)
                return Literal(type=TIMESTAMP,
                               value=base.value * MICROS_PER_DAY + v_iv)
            if isinstance(base, Literal) and base.type == TIMESTAMP \
                    and base.value is not None:
                return Literal(type=TIMESTAMP, value=base.value + v_iv)
            if base.type == TIMESTAMP:
                return call("ts_add_micros", base,
                            Literal(type=BIGINT, value=v_iv))
            if whole_days:
                return call("date_add_days", base,
                            Literal(type=BIGINT,
                                    value=v_iv // MICROS_PER_DAY))
            # sub-day interval promotes the date to a timestamp
            return call("ts_add_micros", call("cast_timestamp", base),
                        Literal(type=BIGINT, value=v_iv))
        months = v_iv
        if isinstance(base, Literal) and base.type == DATE \
                and base.value is not None:
            return Literal(type=DATE,
                           value=_shift_date(base.value, months, "month"))
        if isinstance(base, Literal) and base.type == TIMESTAMP \
                and base.value is not None:
            days = base.value // MICROS_PER_DAY
            tod = base.value - days * MICROS_PER_DAY
            return Literal(
                type=TIMESTAMP,
                value=_shift_date(days, months, "month") * MICROS_PER_DAY
                + tod)
        if base.type == TIMESTAMP:
            return call("ts_add_months", base,
                        Literal(type=BIGINT, value=months))
        return call("date_add_months", base,
                    Literal(type=BIGINT, value=months))

    def _bind_case(self, e: ast.Case, scope: Scope, agg) -> Expr:
        whens = []
        for cond, res in e.whens:
            if e.operand is not None:
                cond = ast.Binary("=", e.operand, cond)
            whens.append((self._bind_impl(cond, scope, agg), self._bind_impl(res, scope, agg)))
        args: List[Expr] = []
        for c, r in whens:
            args.extend([c, r])
        if e.else_ is not None:
            else_ir = self._bind_impl(e.else_, scope, agg)
        else:
            else_ir = Literal(type=whens[0][1].type, value=None)
        args.append(else_ir)
        return call("case", *args)

    def _register_window(self, e: ast.WindowExpr, scope: Scope, agg) -> ColumnRef:
        from presto_tpu.ops.window import WindowFunc

        if e in self._win_slots:
            slot = self._win_slots[e]
            return ColumnRef(type=self._windows[slot][1].type, index=_WIN_BASE + slot)

        fc = e.func
        name = fc.name
        if name not in WINDOW_FUNCTIONS:
            raise BindError(f"unknown window function {name}")
        kind = name
        arg = None
        offset = 1
        if name in ("row_number", "rank", "dense_rank", "percent_rank", "cume_dist"):
            if fc.args:
                raise BindError(f"{name} takes no arguments")
        elif name == "ntile":
            if len(fc.args) != 1:
                raise BindError("ntile takes one argument")
            n_ir = self._bind_impl(fc.args[0], scope, agg)
            if (not isinstance(n_ir, Literal) or n_ir.value is None
                    or int(n_ir.value) < 1):
                raise BindError("ntile bucket count must be a positive literal")
            offset = int(n_ir.value)
        elif name == "count" and (fc.star or not fc.args):
            kind = "count_star"
        else:
            if not fc.args:
                raise BindError(f"{name} requires an argument")
            arg = self._bind_impl(fc.args[0], scope, agg)
            if name in ("lead", "lag", "nth_value") and len(fc.args) > 1:
                off_ir = self._bind_impl(fc.args[1], scope, agg)
                if not isinstance(off_ir, Literal) or off_ir.value is None:
                    raise BindError(f"{name} offset must be a literal")
                offset = int(off_ir.value)
                if name == "nth_value" and offset < 1:
                    raise BindError("nth_value position must be >= 1")
                if offset < 0:
                    raise BindError(f"{name} offset must be non-negative")
        frame = self._bind_frame(e.frame, kind)
        if fc.ignore_nulls and kind not in (
                "lead", "lag", "first_value", "last_value", "nth_value"):
            raise BindError(
                "IGNORE NULLS applies to lead/lag/first_value/"
                "last_value/nth_value only")
        wf = WindowFunc(kind=kind, arg=arg, offset=offset, frame=frame,
                        ignore_nulls=fc.ignore_nulls)
        partition_irs = [self._bind_impl(p, scope, agg) for p in e.partition_by]
        order_irs = [self._bind_impl(o.expr, scope, agg) for o in e.order_by]
        ascending = [o.ascending for o in e.order_by]
        slot = len(self._windows)
        self._windows.append((e, wf, partition_irs, order_irs, ascending))
        self._win_slots[e] = slot
        return ColumnRef(type=wf.type, index=_WIN_BASE + slot)

    def _bind_frame(self, frame, kind: str):
        """AST frame -> WindowFunc.frame. RANGE frames support only the
        unbounded/current bounds (reference parity: 0.208 rejects RANGE
        with value offsets); ROWS frames become signed row offsets."""
        if frame is None:
            return None
        ft, (sk, sn), (ek, en) = frame
        if ft == "range":
            if sk != "unbounded_preceding":
                raise BindError("RANGE frame start must be UNBOUNDED PRECEDING")
            if ek == "current":
                return None  # the default frame
            if ek == "unbounded_following":
                return ("whole",)
            raise BindError("RANGE frame end must be CURRENT ROW or UNBOUNDED FOLLOWING")
        s_off = {"unbounded_preceding": None, "preceding": -sn, "current": 0,
                 "following": sn}.get(sk)
        e_off = {"unbounded_following": None, "preceding": -en, "current": 0,
                 "following": en}.get(ek)
        if sk == "unbounded_following" or ek == "unbounded_preceding":
            raise BindError("invalid ROWS frame bounds")
        if kind in ("min", "max") and s_off is not None:
            raise BindError(f"{kind} supports only UNBOUNDED PRECEDING frame starts")
        if (s_off, e_off) == (None, None):
            return ("whole",)
        return ("rows", s_off, e_off)

    def _attach_windows(self, node: PlanNode) -> Tuple[PlanNode, Dict[int, int]]:
        """Build WindowNode(s) above ``node``, grouping registered
        windows by identical (partition, order) spec; returns the node
        and the sentinel-slot -> real-channel mapping."""
        from presto_tpu.planner.plan import WindowNode

        specs: List[Tuple[tuple, List[int]]] = []  # (spec key, slots)
        for slot, (e, wf, p_irs, o_irs, asc) in enumerate(self._windows):
            key = (tuple(p_irs), tuple(o_irs), tuple(asc))
            for k, slots in specs:
                if k == key:
                    slots.append(slot)
                    break
            else:
                specs.append((key, [slot]))
        base = len(node.channels)
        mapping: Dict[int, int] = {}
        for key, slots in specs:
            p_irs, o_irs, asc = key
            funcs = [self._windows[s][1] for s in slots]
            names = [f"$win{s}" for s in slots]
            for j, s in enumerate(slots):
                mapping[s] = base + j
            node = WindowNode(
                source=node,
                partition_exprs=list(p_irs),
                order_exprs=list(o_irs),
                ascending=list(asc),
                funcs=funcs,
                func_names=names,
            )
            base += len(slots)
        return node, mapping

    def _patch_windows(self, e: Expr, mapping: Dict[int, int]) -> Expr:
        if isinstance(e, ColumnRef):
            if e.index >= _WIN_BASE:
                return ColumnRef(type=e.type, index=mapping[e.index - _WIN_BASE], name=e.name)
            return e
        if isinstance(e, Call):
            return Call(
                type=e.type, fn=e.fn,
                args=tuple(self._patch_windows(a, mapping) for a in e.args),
            )
        from presto_tpu.expr.ir import LambdaExpr

        if isinstance(e, LambdaExpr):
            return LambdaExpr(type=e.type, params=e.params,
                              body=self._patch_windows(e.body, mapping))
        return e

    def _bind_agg_call(self, e: ast.FuncCall, scope: Scope, agg: AggCtx) -> ColumnRef:
        if e.ignore_nulls:
            raise BindError(
                "IGNORE NULLS applies to window value functions "
                "(lead/lag/first_value/last_value/nth_value OVER)")
        from presto_tpu.ops.aggregate import output_type

        if e.star or (e.name == "count" and not e.args):
            a = AggCall(fn="count_star", arg=None, type=BIGINT)
            return agg.agg_ref(a)
        if e.name == "arbitrary":
            # any value per group: the max of the group qualifies
            # (ArbitraryAggregation semantics are "some input value")
            return self._bind_agg_call(
                ast.FuncCall("max", e.args, distinct=e.distinct), scope, agg)
        if e.name == "count_if":
            if len(e.args) != 1:
                raise BindError("count_if takes one argument")
            pred = self._bind(e.args[0], scope)
            a = AggCall(fn="count_star", arg=None, type=BIGINT, filter=pred)
            return agg.agg_ref(a)
        if e.name == "geometric_mean":
            inner = self._bind_agg_call(
                ast.FuncCall("avg", (ast.FuncCall("ln", e.args),)), scope, agg)
            return call("exp", inner)
        fn, distinct = e.name, e.distinct
        if fn == "approx_percentile" and len(e.args) == 2 \
                and isinstance(e.args[1], ast.ArrayCtor):
            # array-of-fractions form: one rank-select per fraction,
            # recomposed as ARRAY[..] (ApproximateLongPercentileArrayAggregations)
            refs = [self._bind_agg_call(
                        ast.FuncCall(fn, (e.args[0], p)), scope, agg)
                    for p in e.args[1].items]
            return call("array_construct", *refs)
        if fn == "approx_percentile" and len(e.args) == 3:
            # weighted form: approx_percentile(x, w, p)
            if distinct:
                raise BindError("approx_percentile DISTINCT unsupported")
            arg = self._bind(e.args[0], scope)
            w = self._bind(e.args[1], scope)
            p_ast = e.args[2]
            arg2 = self._bind(p_ast, scope)
            if not isinstance(arg2, Literal) or arg2.value is None:
                raise BindError("approx_percentile fraction must be a literal")
            p = float(arg2.value) / (10.0 ** (arg2.type.scale or 0)
                                     if arg2.type.is_decimal else 1.0)
            if not 0.0 <= p <= 1.0:
                raise BindError("approx_percentile fraction must be in [0, 1]")
            a = AggCall(fn=fn, arg=arg, type=arg.type,
                        arg2=Literal(type=DOUBLE, value=p), arg3=w)
            return agg.agg_ref(a)
        if fn == "numeric_histogram":
            # numeric_histogram(buckets, x): fixed-width bins over the
            # group's [min, max] span, keys = per-bin value means
            # (NumericHistogramAggregation's Ben-Haim/Tom-Tov role)
            if len(e.args) != 2:
                raise BindError("numeric_histogram takes (buckets, x)")
            b = self._bind(e.args[0], scope)
            if not isinstance(b, Literal) or not b.type.name == "bigint":
                raise BindError("numeric_histogram bucket count must be an "
                                "integer literal")
            from presto_tpu.ops.aggregate import ARRAY_AGG_CAP

            if not 1 <= int(b.value) <= ARRAY_AGG_CAP:
                raise BindError(
                    f"numeric_histogram bucket count must be in "
                    f"[1, {ARRAY_AGG_CAP}]")
            arg = self._bind(e.args[1], scope)
            a = AggCall(fn=fn, arg=arg, type=arg.type, arg2=b)
            a = dataclasses.replace(a, type=output_type(a))
            return agg.agg_ref(a)
        if fn in ("min", "max") and len(e.args) == 2:
            # max(x, n) / min(x, n): the n extreme values as an array,
            # descending for max, ascending for min
            # (Max/MinNAggregationFunction.java)
            if distinct:
                raise BindError(f"DISTINCT unsupported for {fn}(x, n)")
            arg = self._bind(e.args[0], scope)
            nn = self._bind(e.args[1], scope)
            self._check_topn_count(fn, nn)
            if not (arg.type.is_numeric or arg.type.name in
                    ("date", "timestamp", "time")) or arg.type.is_long_decimal:
                raise BindError(
                    f"{fn}(x, n) requires a fixed-width orderable x "
                    f"(got {arg.type})")
            a = AggCall(fn=f"{fn}_n", arg=arg, type=arg.type, arg2=nn)
            a = dataclasses.replace(a, type=output_type(a))
            return agg.agg_ref(a)
        if fn in ("min_by", "max_by") and len(e.args) == 3:
            # max_by(x, y, n) / min_by(x, y, n): the x values paired
            # with the n extreme y keys (Max/MinByNAggregationFunction)
            if distinct:
                raise BindError(f"DISTINCT unsupported for {fn}(x, y, n)")
            arg = self._bind(e.args[0], scope)
            key = self._bind(e.args[1], scope)
            nn = self._bind(e.args[2], scope)
            self._check_topn_count(fn, nn)
            for name, t in (("x", arg.type), ("y", key.type)):
                if not (t.is_numeric or t.name in
                        ("date", "timestamp", "time")) or t.is_long_decimal:
                    raise BindError(
                        f"{fn}(x, y, n) requires fixed-width orderable "
                        f"arguments (got {t} for {name})")
            a = AggCall(fn=f"{fn}_n", arg=arg, type=arg.type, arg2=key,
                        arg3=nn)
            a = dataclasses.replace(a, type=output_type(a))
            return agg.agg_ref(a)
        if fn == "map_union":
            if len(e.args) != 1:
                raise BindError("map_union takes one argument")
            if distinct:
                raise BindError("DISTINCT unsupported for map_union")
            arg = self._bind(e.args[0], scope)
            # strictly scalar-valued maps: is_map also admits HLL
            # sketches (union those via merge()) and multimap results,
            # whose array-valued lanes this kernel cannot slice
            if arg.type.name != "map" or (
                    arg.type.element is not None and arg.type.element.is_array):
                raise BindError(
                    f"map_union requires a scalar-valued map argument "
                    f"(got {arg.type})")
            a = AggCall(fn=fn, arg=arg, type=arg.type)
            a = dataclasses.replace(a, type=output_type(a))
            return agg.agg_ref(a)
        if fn in ("learn_libsvm_regressor", "learn_libsvm_classifier"):
            # libsvm-parameterized variants (presto-ml
            # LearnLibSvm*Aggregation): the params string configures a
            # libsvm trainer there; the trainers here are the
            # closed-form TPU redesigns (normal equations / Gaussian
            # NB), so the params argument is accepted and ignored
            if len(e.args) == 3:
                e = dataclasses.replace(e, args=e.args[:2])
            fn = fn.replace("_libsvm", "")
        if fn in ("min_by", "max_by", "approx_percentile", "map_agg",
                  "multimap_agg",
                  "covar_pop", "covar_samp", "corr", "regr_slope",
                  "regr_intercept",
                  "learn_regressor", "learn_classifier",
                  "evaluate_classifier_predictions"):
            if len(e.args) != 2:
                raise BindError(f"aggregate {fn} takes two arguments")
            if distinct:
                raise BindError(f"DISTINCT unsupported for {fn}")
            arg = self._bind(e.args[0], scope)
            arg2 = self._bind(e.args[1], scope)
            if fn == "approx_percentile":
                if not isinstance(arg2, Literal) or arg2.value is None:
                    raise BindError("approx_percentile fraction must be a literal")
                p = float(arg2.value) / (10.0 ** (arg2.type.scale or 0)
                                         if arg2.type.is_decimal else 1.0)
                if not 0.0 <= p <= 1.0:
                    raise BindError("approx_percentile fraction must be in [0, 1]")
                arg2 = Literal(type=DOUBLE, value=p)
            a = AggCall(fn=fn, arg=arg, type=arg.type, distinct=distinct, arg2=arg2)
            a = dataclasses.replace(a, type=output_type(a))
            return agg.agg_ref(a)
        if len(e.args) != 1:
            raise BindError(f"aggregate {e.name} takes one argument")
        arg = self._bind(e.args[0], scope)
        a = AggCall(fn=fn, arg=arg, type=arg.type, distinct=distinct)
        a = AggCall(fn=a.fn, arg=a.arg, type=output_type(a), distinct=a.distinct)
        return agg.agg_ref(a)

    @staticmethod
    def _fold_literal_call(fn, args):
        """Constant-fold scalar calls whose column forms run through
        dictionary LUTs — with literal arguments there is no dictionary
        to transform, so the value computes at bind time (the
        reference's constant folding in ExpressionInterpreter.java)."""
        from presto_tpu.expr.compile import (
            STRING_TRANSFORM_FNS, _levenshtein, _string_transform,
            iso_date_days, mysql_datetime_micros, xxh64_signed,
        )

        def lit_val(a):
            return a.value if isinstance(a, Literal) else None

        if fn in ("crc32", "xxhash64") and len(args) == 1 \
                and isinstance(args[0], Call) and args[0].fn == "to_utf8" \
                and isinstance(args[0].args[0], Literal):
            s = args[0].args[0].value
            if s is None:
                return Literal(type=BIGINT, value=None)
            import zlib

            if fn == "crc32":
                return Literal(type=BIGINT, value=zlib.crc32(s.encode()))
            return Literal(type=BIGINT, value=xxh64_signed(s.encode()))
        if not args or not all(isinstance(a, Literal) for a in args):
            return None
        v0 = lit_val(args[0])
        _null_out = {"from_base": BIGINT, "levenshtein_distance": BIGINT,
                     "hamming_distance": BIGINT, "date_parse": TIMESTAMP,
                     "from_iso8601_date": DATE, "json_size": BIGINT}
        if fn in _null_out and any(a.value is None for a in args):
            # NULL in ANY argument is NULL out (reference convention)
            return Literal(type=_null_out[fn], value=None)
        if fn in STRING_TRANSFORM_FNS and isinstance(v0, (str, type(None))) \
                and args[0].type.is_string:
            if any(a.value is None for a in args):
                return Literal(type=VARCHAR, value=None)
            tf = _string_transform(Call(type=args[0].type, fn=fn,
                                        args=tuple(args)))
            if tf is None:
                return None
            f, _ = tf
            out = None if v0 is None else f(v0)
            return Literal(type=VARCHAR, value=out)
        if v0 is None:
            return None
        if fn == "json_size":
            from presto_tpu.expr.compile import _json_path_lookup

            found, got = _json_path_lookup(v0, args[1].value)
            if not found:
                return Literal(type=BIGINT, value=None)
            return Literal(
                type=BIGINT,
                value=len(got) if isinstance(got, (dict, list)) else 0)
        if fn == "from_base":
            try:
                return Literal(type=BIGINT,
                               value=int(v0, int(args[1].value)))
            except ValueError as ex:
                raise BindError(f"from_base: {ex}")
        if fn == "date_parse":
            return Literal(type=TIMESTAMP,
                           value=mysql_datetime_micros(v0, args[1].value))
        if fn == "from_iso8601_date":
            return Literal(type=DATE, value=iso_date_days(v0))
        if fn == "levenshtein_distance":
            return Literal(type=BIGINT,
                           value=_levenshtein(v0, args[1].value))
        if fn == "hamming_distance":
            b = args[1].value
            if len(v0) != len(b):
                # deviation (documented): NULL where the reference
                # raises — matches the column LUT path
                return Literal(type=BIGINT, value=None)
            return Literal(type=BIGINT,
                           value=sum(x != y for x, y in zip(v0, b)))
        return None

    @staticmethod
    def _check_topn_count(fn, nn):
        """n of max/min(x, n) and max_by/min_by(x, y, n) must be a
        positive integer literal within the container cap."""
        from presto_tpu.ops.aggregate import ARRAY_AGG_CAP

        if not isinstance(nn, Literal) or nn.value is None \
                or not nn.type.is_integerlike:
            raise BindError(f"{fn}'s n must be an integer literal")
        if not 1 <= int(nn.value) <= ARRAY_AGG_CAP:
            raise BindError(f"{fn}'s n must be in [1, {ARRAY_AGG_CAP}]")

    # ------------------------------------------------------------------
    def _substitute_aliases(self, e: ast.Node, alias_map: Dict[str, ast.Node],
                            scope) -> ast.Node:
        """Replace bare identifiers that name a select alias (and do NOT
        resolve as real columns — columns win) with the aliased
        expression; descends expressions but not subquery bodies."""
        if isinstance(e, ast.Identifier) and e.qualifier is None \
                and e.name in alias_map:
            try:
                scope.resolve(None, e.name)
                return e  # a real column shadows the alias
            except Exception:
                return alias_map[e.name]
        if isinstance(e, (ast.Query, ast.Union, ast.ScalarSubquery,
                          ast.Exists, ast.InSubquery)):
            return e
        if dataclasses.is_dataclass(e) and isinstance(e, ast.Node):
            changes = {}
            for f in dataclasses.fields(e):
                v = getattr(e, f.name)
                nv = self._sub_alias_value(v, alias_map, scope)
                if nv is not v:
                    changes[f.name] = nv
            return dataclasses.replace(e, **changes) if changes else e
        return e

    def _sub_alias_value(self, v, alias_map, scope):
        if isinstance(v, ast.Node):
            return self._substitute_aliases(v, alias_map, scope)
        if isinstance(v, tuple):
            out = tuple(self._sub_alias_value(x, alias_map, scope) for x in v)
            return out if any(a is not b for a, b in zip(out, v)) else v
        return v

    def _bind_order(self, order_items, items, out_irs, scope) -> List[Expr]:
        order_irs: List[Expr] = []
        for o in order_items:
            e = o.expr
            if isinstance(e, ast.NumberLit):
                order_irs.append(out_irs[int(e.text) - 1])
                continue
            hit = next(
                (out_irs[i] for i, (se, n) in enumerate(items)
                 if (isinstance(e, ast.Identifier) and e.qualifier is None and e.name == n) or se == e),
                None,
            )
            if hit is not None:
                order_irs.append(hit)
            else:
                order_irs.append(self._bind(e, scope))
        return order_irs


def _is_row_ast(e: ast.Node) -> bool:
    """Row-constructor syntax: (a, b) or row(a, b)."""
    return isinstance(e, ast.RowCtor) or (
        isinstance(e, ast.FuncCall) and e.name == "row" and bool(e.args))


def _row_items(e: ast.Node):
    return e.items if isinstance(e, ast.RowCtor) else e.args


def _row_comparison(left: ast.Node, right: ast.Node, op: str) -> ast.Node:
    """(a, b) = (c, d) -> a = c AND b = d; <> negates the conjunction.
    Accepts both the (a, b) and row(a, b) constructor forms."""
    if not (_is_row_ast(left) and _is_row_ast(right)):
        raise BindError("row comparison needs row constructors on both sides")
    left = ast.RowCtor(tuple(_row_items(left)))
    right = ast.RowCtor(tuple(_row_items(right)))
    if len(left.items) != len(right.items):
        raise BindError(
            f"row arity mismatch: {len(left.items)} vs {len(right.items)}")
    conj = None
    for l, r in zip(left.items, right.items):
        eq = ast.Binary("=", l, r)
        conj = eq if conj is None else ast.Binary("and", conj, eq)
    return ast.Unary("not", conj) if op == "<>" else conj


def term_of_ref(terms: List[Term], ref: int) -> int:
    for i, t in enumerate(terms):
        if t.offset <= ref < t.offset + len(t.scope):
            return i
    raise BindError(
        f"internal: channel reference ${ref} falls outside every join "
        "term's scope (binder channel-offset bug)")
