"""Interval × null-state × NaN abstract domain over the expression IR.

Reference analog: the soundness the reference gets from *checked*
bytecode — every generated arithmetic op raises ARITHMETIC_OVERFLOW /
DIVISION_BY_ZERO / INVALID_CAST_ARGUMENT instead of wrapping
(sql/gen/ExpressionCompiler + operator/scalar/*Operators.java).  Our
jnp kernels can't raise from inside a jitted program, so the same
guarantee is split in two: kernels NULL the offending lanes (the
engine's established deviation family, like div-by-zero), and THIS
module proves where that can happen before execution.

:class:`AbstractValue` is one lattice element: a closed interval
``[lo, hi]`` over the *device representation* (scaled ints for short
decimals, epoch days/micros for DATE/TIMESTAMP, dictionary codes for
varchar), a ``may_null`` bit, a ``may_nan`` bit for floats, and a
``known`` evidence bit — True when the interval derives from actual
evidence (literals, connector zone-map domains, VALUES rows), False
when it is merely the type contract.  Checkers only *fail* on known
intervals; assumed ones widen conservatively and surface as warnings
at aggregation folds (see kernel_soundness.py).

Every transfer function here MUST over-approximate its kernel: the
``PRESTO_TPU_RANGE_SANITIZER=1`` runtime cross-check samples observed
column min/max at page boundaries and fails loudly when a value
escapes its predicted interval, so an under-approximating rule is a
caught bug, not a silent soundness hole.

Pure python (no jax import): the analyzer runs at plan time, host-side.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.expr.ir import (
    AggCall,
    Call,
    ColumnRef,
    Expr,
    LambdaExpr,
    LambdaVar,
    Literal,
)
from presto_tpu.types import Type

INF = math.inf

# device-width integer bounds (the wrap points of the jnp kernels —
# distinct from declared SQL bounds: a DECIMAL(12,2) column is stored
# in int64 lanes and physically wraps at I64, not at 10^12)
I8 = (-(1 << 7), (1 << 7) - 1)
I16 = (-(1 << 15), (1 << 15) - 1)
I32 = (-(1 << 31), (1 << 31) - 1)
I64 = (-(1 << 63), (1 << 63) - 1)


@dataclasses.dataclass(frozen=True)
class AbstractValue:
    """One element of the interval × null × nan lattice."""

    lo: float  # -inf = unbounded below (finite bounds stay exact ints)
    hi: float  # +inf = unbounded above
    may_null: bool = True
    may_nan: bool = False
    #: evidence bit: True = derived from literals/stats, False = the
    #: type contract alone (checkers do not fail on assumed intervals)
    known: bool = False

    def join(self, other: "AbstractValue") -> "AbstractValue":
        """Least upper bound (CASE/COALESCE/UNION branches)."""
        return AbstractValue(
            min(self.lo, other.lo), max(self.hi, other.hi),
            self.may_null or other.may_null,
            self.may_nan or other.may_nan,
            self.known and other.known)

    def contains(self, v) -> bool:
        return self.lo <= v <= self.hi


def top(t: Type, may_null: bool = True) -> AbstractValue:
    """The type contract alone (assumed, not evidence)."""
    lo, hi = type_bounds(t)
    return AbstractValue(lo, hi, may_null=may_null,
                         may_nan=t.name in ("double", "real"), known=False)


def type_bounds(t: Type):
    """Representable device-repr bounds of ``t`` (see module doc)."""
    n = t.name
    if n == "boolean":
        return (0, 1)
    if n == "tinyint":
        return I8
    if n == "smallint":
        return I16
    if n in ("integer", "date"):
        return I32
    if t.is_decimal:
        # declared bound, clipped to the storage width: short decimals
        # live in int64 lanes, long/wide in limb vectors that cover p
        m = 10 ** (t.precision or 38) - 1
        if not t.is_long_decimal:
            m = min(m, I64[1])
        return (-m, m)
    if n in ("bigint", "timestamp", "time") or n.startswith("interval"):
        return I64
    if n in ("double", "real"):
        return (-INF, INF)
    if t.is_string and not t.is_raw_string:
        return (0, INF)  # dictionary codes are non-negative
    return (-INF, INF)


def device_int_bounds(t: Type):
    """Where the kernel physically wraps: the int lane width backing
    ``t``, or None for types whose ops don't wrap (floats, limbs)."""
    n = t.name
    if n == "tinyint":
        return I8
    if n == "smallint":
        return I16
    if n in ("integer", "date"):
        return I32
    if t.is_decimal and not t.is_long_decimal:
        return I64
    if n in ("bigint", "timestamp", "time") or n.startswith("interval"):
        return I64
    return None


def from_literal(e: Literal) -> AbstractValue:
    v = e.value
    if v is None:
        return AbstractValue(0, 0, may_null=True, known=True)
    if isinstance(v, bool):
        v = int(v)
    if isinstance(v, float):
        if math.isnan(v):
            return AbstractValue(-INF, INF, may_null=False, may_nan=True,
                                 known=True)
        return AbstractValue(v, v, may_null=False, known=True)
    if isinstance(v, int):
        return AbstractValue(v, v, may_null=False, known=True)
    # strings resolve to dictionary codes at compile time — unknown here
    return top(e.type, may_null=False)


def from_channel(t: Type, domain=None) -> AbstractValue:
    """Scan-channel seed: zone-map ``Channel.domain`` is evidence (the
    connector's declared min/max in device repr), the bare type is not."""
    if domain is not None:
        lo, hi = domain
        return AbstractValue(lo, hi, may_null=True, known=True)
    return top(t)


# ---------------------------------------------------------------------------
# None-free interval arithmetic (±inf sentinels, exact ints when finite)
# ---------------------------------------------------------------------------

def _times(x, y):
    # standard interval convention: 0 × ±inf = 0 (the unbounded
    # directions are covered by the other corner products)
    if x == 0 or y == 0:
        return 0
    return x * y


def iv_add(a: AbstractValue, b: AbstractValue):
    return (a.lo + b.lo, a.hi + b.hi)


def iv_sub(a: AbstractValue, b: AbstractValue):
    return (a.lo - b.hi, a.hi - b.lo)


def iv_mul(a: AbstractValue, b: AbstractValue):
    c = [_times(a.lo, b.lo), _times(a.lo, b.hi),
         _times(a.hi, b.lo), _times(a.hi, b.hi)]
    return (min(c), max(c))


def iv_neg(a: AbstractValue):
    return (-a.hi, -a.lo)


def iv_abs(a: AbstractValue):
    if a.lo >= 0:
        return (a.lo, a.hi)
    if a.hi <= 0:
        return (-a.hi, -a.lo)
    return (0, max(-a.lo, a.hi))


def iv_div(a: AbstractValue, b: AbstractValue, trunc: bool):
    """Quotient interval EXCLUDING the zero divisor (those lanes are
    NULLed by the kernel guard; reference raises DIVISION_BY_ZERO)."""
    blo, bhi = b.lo, b.hi
    if blo == 0 and bhi == 0:
        return (0, 0)  # every lane nulls
    # divisor magnitude >= 1 once 0 is excluded (integer/scaled lanes)
    cands = []
    for bb in {blo, bhi, -1 if blo < 0 < bhi or blo == 0 or bhi == 0 else None,
               1 if blo < 0 < bhi or blo == 0 or bhi == 0 else None}:
        if bb is None or bb == 0:
            continue
        for aa in (a.lo, a.hi):
            if aa in (-INF, INF):
                cands.append(-INF if (aa < 0) == (bb > 0) else INF)
            elif bb in (-INF, INF):
                cands.append(0)
            else:
                q = abs(aa) // abs(bb)
                cands.append(-q if (aa < 0) != (bb < 0) else q)
    if not cands:
        return (0, 0)
    return (min(cands), max(cands))


def iv_mod(a: AbstractValue, b: AbstractValue):
    """SQL mod takes the dividend's sign; |r| < |b|."""
    m = max(abs(b.lo), abs(b.hi))
    if m in (0,):
        return (0, 0)
    m = m - 1 if m not in (INF,) else INF
    m = min(m, max(abs(a.lo), abs(a.hi)))
    lo = -m if a.lo < 0 else 0
    hi = m if a.hi > 0 else 0
    return (lo, hi)


# ---------------------------------------------------------------------------
# per-fn transfer catalog
# ---------------------------------------------------------------------------

#: calendar-field output ranges (exact by construction of the civil
#: calendar kernels in expr/compile.py)
_DATEPART_RANGES = {
    "year": (-290308, 294247),  # int64 micros span
    "month": (1, 12), "day": (1, 31), "quarter": (1, 4),
    "day_of_week": (1, 7), "day_of_year": (1, 366),
    "week": (1, 53), "year_of_week": (-290308, 294247),
    "hour": (0, 23), "minute": (0, 59), "second": (0, 59),
    "millisecond": (0, 999),
}

_BOOL_FNS = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not",
    "like", "in", "between", "is_null", "not_null",
    "regexp_like", "starts_with", "ends_with", "is_json_scalar",
    "is_nan", "is_finite", "is_infinite", "contains", "arrays_overlap",
    "any_match", "none_match", "all_match", "st_contains",
})

_UNIT_FRACTION_FNS = frozenset({"rand", "random"})


def _scale_of(t: Type) -> int:
    return t.scale if t.is_decimal else 0


def _rescale_iv(lo, hi, from_scale: int, to_scale: int):
    if to_scale > from_scale:
        f = 10 ** (to_scale - from_scale)
        return (_times(lo, f), _times(hi, f))
    if to_scale < from_scale:
        f = 10 ** (from_scale - to_scale)
        return (-(abs(lo) // f) if lo < 0 else lo // f,
                -(abs(hi) // f) if hi < 0 else hi // f)
    return (lo, hi)


def _coerced(args: Sequence[AbstractValue], arg_types: Sequence[Type],
             out_type: Type) -> Optional[List[AbstractValue]]:
    """``args`` as the compiler's ``_coerce`` brings value operands to
    ``out_type`` (the branches of if/case/coalesce, greatest/least): a
    decimal or integer operand of a decimal result is up-scaled, of a
    double result divided by its scale.  None for a coercion without a
    rule here (the caller falls back to the type contract)."""
    out = []
    for a, t in zip(args, arg_types):
        if t == out_type:
            out.append(a)
        elif out_type.name in ("double", "real") \
                and t.value_shape == () and not t.is_string:
            f = 10.0 ** _scale_of(t)
            out.append(dataclasses.replace(
                a, lo=a.lo / f if a.lo != -INF else a.lo,
                hi=a.hi / f if a.hi != INF else a.hi))
        elif out_type.is_decimal and not t.is_long_decimal \
                and (t.is_decimal or device_int_bounds(t) is not None) \
                and _scale_of(t) <= out_type.scale:
            lo, hi = _rescale_iv(a.lo, a.hi, _scale_of(t), out_type.scale)
            out.append(dataclasses.replace(a, lo=lo, hi=hi))
        elif device_int_bounds(out_type) is not None \
                and not out_type.is_decimal and not t.is_decimal \
                and device_int_bounds(t) is not None \
                and t.name not in ("date", "timestamp", "time"):
            out.append(a)  # integer widening keeps the value
        else:
            return None
    return out


def transfer(fn: str, out_type: Type, args: Sequence[AbstractValue],
             arg_types: Sequence[Type]):
    """Raw (pre-clamp) result interval of ``fn`` plus null/nan bits, as
    an AbstractValue whose interval may ESCAPE ``out_type``'s device
    bounds — the caller compares against :func:`device_int_bounds` to
    flag overflow hazards, then clamps (escaped lanes are NULLed by the
    kernel guards, so in-flight values stay inside the clamp).
    """
    known = all(a.known for a in args) if args else False
    strict_null = any(a.may_null for a in args)
    nan_in = any(a.may_nan for a in args)

    if fn == "try":
        # runtime identity — trapped lanes surface as NULL
        return dataclasses.replace(args[0], may_null=True)

    if fn in _BOOL_FNS:
        # 3VL and/or can absorb NULL (definite false/true resurrects);
        # is_null/not_null never return NULL
        may_null = strict_null and fn not in ("is_null", "not_null")
        return AbstractValue(0, 1, may_null=may_null, known=known)

    if fn in ("add", "sub", "mul", "neg", "abs"):
        a = args[0]
        if fn == "neg":
            lo, hi = iv_neg(a)
        elif fn == "abs":
            lo, hi = iv_abs(a)
        else:
            b = args[1]
            sa, sb = _scale_of(arg_types[0]), _scale_of(arg_types[1])
            so = _scale_of(out_type)
            if fn == "mul":
                lo, hi = iv_mul(a, b)  # scales add: sa+sb == so
            else:
                ra = AbstractValue(*_rescale_iv(a.lo, a.hi, sa, so),
                                   may_null=a.may_null, known=a.known)
                rb = AbstractValue(*_rescale_iv(b.lo, b.hi, sb, so),
                                   may_null=b.may_null, known=b.known)
                lo, hi = iv_add(ra, rb) if fn == "add" else iv_sub(ra, rb)
        return AbstractValue(lo, hi, may_null=strict_null,
                             may_nan=nan_in, known=known)

    if fn == "div":
        if out_type.name in ("double", "real"):
            return AbstractValue(-INF, INF, may_null=True, may_nan=True,
                                 known=False)
        lo, hi = iv_div(args[0], args[1], trunc=True)
        return AbstractValue(lo, hi, may_null=True, known=known)
    if fn == "mod":
        lo, hi = iv_mod(args[0], args[1])
        return AbstractValue(lo, hi, may_null=True, known=known)

    if fn in ("cast_bigint", "cast_smallint", "cast_tinyint"):
        a = args[0]
        t0 = arg_types[0]
        if t0.is_string or t0.name in ("double", "real"):
            # parse/round casts: bounded by the target width only;
            # unparseable strings NULL (documented deviation)
            return AbstractValue(*type_bounds(out_type),
                                 may_null=True, known=False)
        lo, hi = _rescale_iv(a.lo, a.hi, _scale_of(t0), 0)
        if t0.is_decimal:
            # HALF_UP rounding can move one unit away from zero
            lo, hi = lo - 1, hi + 1
        return AbstractValue(lo, hi, may_null=strict_null, known=a.known)
    if fn == "cast_decimal":
        a = args[0]
        t0 = arg_types[0]
        if t0.name in ("double", "real") or t0.is_string:
            return AbstractValue(*type_bounds(out_type),
                                 may_null=strict_null, known=False)
        lo, hi = _rescale_iv(a.lo, a.hi, _scale_of(t0), out_type.scale)
        if out_type.scale < _scale_of(t0):
            # the kernel's down-scale floors (``_rescale``) where
            # ``_rescale_iv`` truncates: one unit of slack each way
            lo, hi = lo - 1, hi + 1
        return AbstractValue(lo, hi, may_null=strict_null, known=a.known)
    if fn in ("cast_double", "to_unixtime"):
        a = args[0]
        s = 10.0 ** _scale_of(arg_types[0]) if arg_types[0].is_decimal else 1.0
        if fn == "to_unixtime":
            s = 1e6 if arg_types[0].name != "date" else 1.0 / 86400.0
        lo = a.lo / s if a.lo not in (-INF, INF) else a.lo
        hi = a.hi / s if a.hi not in (-INF, INF) else a.hi
        return AbstractValue(lo, hi, may_null=strict_null,
                             may_nan=nan_in, known=a.known)
    if fn == "cast_real":
        return AbstractValue(-INF, INF, may_null=strict_null, may_nan=True,
                             known=False)
    if fn in ("cast_date", "cast_timestamp", "cast_time", "from_unixtime",
              "date_trunc", "date_add", "date_add_days", "date_add_months",
              "ts_add_micros", "ts_add_months"):
        # calendar moves: conservative type contract (trunc shrinks,
        # adds shift by data-dependent amounts)
        return AbstractValue(*type_bounds(out_type), may_null=strict_null,
                             known=False)

    if fn in _DATEPART_RANGES:
        lo, hi = _DATEPART_RANGES[fn]
        return AbstractValue(lo, hi, may_null=strict_null, known=True)
    if fn == "last_day_of_month":
        return AbstractValue(*I32, may_null=strict_null, known=False)

    if fn == "sign":
        return AbstractValue(-1, 1, may_null=strict_null, known=True)
    if fn in ("ceil", "ceiling", "floor", "round", "truncate"):
        a = args[0]
        t0 = arg_types[0]
        if t0.is_decimal:
            lo, hi = _rescale_iv(a.lo, a.hi, t0.scale, _scale_of(out_type))
            lo, hi = lo - 1, hi + 1  # rounding slack
            return AbstractValue(lo, hi, may_null=strict_null, known=a.known)
        if t0.name in ("double", "real"):
            return AbstractValue(a.lo - 1, a.hi + 1, may_null=strict_null,
                                 may_nan=nan_in, known=a.known)
        return AbstractValue(a.lo, a.hi, may_null=strict_null, known=a.known)
    if fn == "sqrt":
        return AbstractValue(0, INF, may_null=strict_null, may_nan=True,
                             known=False)
    if fn in ("exp", "cosh"):
        return AbstractValue(0, INF, may_null=strict_null, may_nan=nan_in,
                             known=False)
    if fn in ("sin", "cos", "tanh"):
        return AbstractValue(-1, 1, may_null=strict_null, may_nan=True,
                             known=False)
    if fn in ("asin", "acos", "atan", "atan2"):
        return AbstractValue(-math.pi, math.pi, may_null=strict_null,
                             may_nan=True, known=False)
    if fn in ("ln", "log10", "log2", "cbrt", "tan", "sinh",
              "degrees", "radians", "power", "pow", "nan", "infinity"):
        return AbstractValue(-INF, INF, may_null=strict_null, may_nan=True,
                             known=False)
    if fn == "width_bucket":
        return AbstractValue(0, INF, may_null=strict_null, known=False)

    if fn in ("greatest", "least"):
        vals = _coerced(args, arg_types, out_type)
        if vals is None:
            return top(out_type)
        lo = (max if fn == "greatest" else min)(a.lo for a in vals)
        hi = (max if fn == "greatest" else min)(a.hi for a in vals)
        # NULL if ANY argument is NULL (kernel parity)
        return AbstractValue(lo, hi, may_null=strict_null,
                             may_nan=nan_in, known=known)

    if fn == "coalesce":
        vals = _coerced(args, arg_types, out_type)
        if vals is None:
            return top(out_type)
        out = vals[0]
        for a in vals[1:]:
            out = out.join(a)
        return AbstractValue(out.lo, out.hi,
                             may_null=all(a.may_null for a in args),
                             may_nan=out.may_nan, known=known)
    if fn in ("if", "case"):
        # if: cond, then, else? (missing else -> NULL);
        # case: when1, then1, ..., else
        if fn == "if":
            conds, picks = [0], list(range(1, len(args)))
        else:
            conds = list(range(0, len(args) - 1, 2))
            picks = list(range(1, len(args) - 1, 2)) + [len(args) - 1]
        branches = _coerced([args[i] for i in picks],
                            [arg_types[i] for i in picks], out_type)
        if branches is None:
            return top(out_type)
        branches = branches or [AbstractValue(0, 0, may_null=True)]
        out = branches[0]
        for a in branches[1:]:
            out = out.join(a)
        may_null = (any(a.may_null for a in branches)
                    or (fn == "if" and len(args) < 3)
                    or any(args[i].may_null for i in conds))
        return AbstractValue(out.lo, out.hi, may_null=may_null,
                             may_nan=out.may_nan,
                             known=all(a.known for a in branches))
    if fn == "nullif":
        a = args[0]
        return AbstractValue(a.lo, a.hi, may_null=True,
                             may_nan=a.may_nan, known=a.known)

    if fn in ("length", "strpos", "codepoint", "json_array_length",
              "url_extract_port", "levenshtein_distance",
              "hamming_distance", "json_size", "cardinality", "bit_count",
              "from_base", "hll_bucket", "hll_rho"):
        hi = 64 if fn == "bit_count" else INF
        lo, may_null = (0, strict_null)
        if fn in ("json_array_length", "url_extract_port", "from_base",
                  "json_size"):
            may_null = True  # parse failures NULL
        if fn == "from_base":
            lo, hi = I64
        return AbstractValue(lo, hi, may_null=may_null, known=False)

    if fn in ("bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
              "bitwise_shift_left", "bitwise_shift_right",
              "crc32", "xxhash64", "checksum"):
        return AbstractValue(*I64, may_null=strict_null, known=False)

    # default: the output type contract, strict nulls — a sound
    # over-approximation for every remaining scalar kernel
    return AbstractValue(*type_bounds(out_type), may_null=True,
                         may_nan=out_type.name in ("double", "real"),
                         known=False)


# ---------------------------------------------------------------------------
# null-effect model (the analyzer's independent view of each kernel
# family's mask behavior; cross-checked against the declared
# expr.compile.NULL_POLICY table by kernel_soundness.check_null_policy)
# ---------------------------------------------------------------------------

#: kernels that can produce NULL from all-non-NULL inputs (overflow /
#: zero-divisor / parse-failure / out-of-range guards NULL the lane —
#: the engine's documented deviation family where the reference raises)
NULL_GENERATING_FNS = frozenset({
    "add", "sub", "mul", "neg", "abs",       # overflow -> NULL
    "div", "mod",                            # zero divisor -> NULL
    "cast_smallint", "cast_tinyint",         # out-of-range -> NULL
    "cast_bigint", "cast_double",            # varchar parse -> NULL
    "nullif",
    "subscript", "element_at",               # out-of-bounds -> NULL
    "json_extract", "json_extract_scalar", "json_array_length",
    "json_size", "json_parse",
    "url_extract_host", "url_extract_path", "url_extract_port",
    "url_extract_protocol", "url_extract_query", "url_decode",
    "regexp_extract", "from_base", "date_parse", "from_iso8601_date",
    "split_part", "array_min", "array_max", "array_sum", "array_average",
    "reduce", "map_concat", "strpos", "width_bucket", "from_unixtime",
})

#: kernels whose output validity is DERIVED, not intersected: they can
#: return non-NULL from NULL inputs (3VL short-circuits, conditionals,
#: null tests)
NULL_ABSORBING_FNS = frozenset({
    "and", "or", "coalesce", "if", "case",
    "is_null", "not_null",
    # compiles to and(ge, le): the 3VL short-circuit can produce FALSE
    # from a NULL bound when the other comparison already fails
    "between",
})


def null_effect(fn: str) -> str:
    """The model's minimal policy class for ``fn``:
    ``generating`` | ``preserving`` | ``strict``."""
    if fn in NULL_GENERATING_FNS:
        return "generating"
    if fn in NULL_ABSORBING_FNS:
        return "preserving"
    return "strict"


# ---------------------------------------------------------------------------
# expression evaluation
# ---------------------------------------------------------------------------

def eval_expr(e: Expr, env: List[AbstractValue],
              on_hazard: Optional[Callable] = None,
              memo: Optional[Dict[int, AbstractValue]] = None
              ) -> AbstractValue:
    """Abstract value of ``e`` over per-channel values ``env``.

    ``on_hazard(kind, expr, raw, bounds)`` fires for every device-width
    escape found along the way (``kind`` ∈ {"overflow", "lossy-cast",
    "division"}); the returned value is already clamped to the device
    width (escaped lanes NULL at runtime, so in-flight values can't
    exceed it).  ``memo`` (by ``id``, for one ``env`` and while the
    caller keeps the expressions alive) makes a walk that asks for
    every sub-expression linear; hazards are not reported through it."""
    if memo is not None:
        got = memo.get(id(e))
        if got is None:
            got = memo[id(e)] = _eval_expr(e, env, None, memo)
        return got
    return _eval_expr(e, env, on_hazard, None)


def _eval_expr(e: Expr, env: List[AbstractValue],
               on_hazard: Optional[Callable],
               memo: Optional[Dict[int, AbstractValue]]) -> AbstractValue:
    if isinstance(e, Literal):
        return from_literal(e)
    if isinstance(e, ColumnRef):
        if 0 <= e.index < len(env):
            return env[e.index]
        return top(e.type)
    if isinstance(e, LambdaVar):
        return top(e.type)
    if isinstance(e, LambdaExpr):
        if e.body is not None:
            # element lanes are unknown: evaluate the body over TOP so
            # nested hazards (literal div 0 inside a lambda) still fire
            eval_expr(e.body, [], on_hazard)
        return top(e.type)
    if isinstance(e, AggCall):
        for sub in (e.arg, e.arg2, e.arg3, e.filter):
            if sub is not None:
                eval_expr(sub, env, on_hazard)
        return top(e.type)
    if not isinstance(e, Call):
        return top(e.type)

    if e.fn == "try":
        # TRY subtree: the reference returns NULL exactly where our
        # kernels NULL the lane, so trappable escapes beneath are not
        # deviations — evaluate without hazard reporting
        v = eval_expr(e.args[0], env, None, memo)
        return dataclasses.replace(v, may_null=True)

    args = [eval_expr(a, env, on_hazard, memo) for a in e.args]
    arg_types = [a.type for a in e.args]
    raw = transfer(e.fn, e.type, args, arg_types)

    if on_hazard is not None:
        _report_hazards(e, args, arg_types, raw, on_hazard)

    # clamp to the device width: escaped lanes are NULLed by the kernel
    # guards, so downstream propagation stays inside the lane bounds.
    # A kernel WITHOUT such a guard (an up-scaling decimal cast) wraps
    # its escaped lanes to anywhere in the lane, and they stay valid:
    # all that is known of them is the lane itself.
    dev = device_int_bounds(e.type)
    if dev is not None and (raw.lo < dev[0] or raw.hi > dev[1]):
        lo, hi = dev
        if e.fn in _NULLS_ESCAPED_LANES:
            lo, hi = max(raw.lo, lo), min(raw.hi, hi)
        raw = AbstractValue(lo, hi, may_null=True, may_nan=raw.may_nan,
                            known=raw.known)
    return raw


#: kernels whose guard NULLs a lane that escaped the device width
#: (expr/compile.py: ``_ovf_*``, ``_rescale_guard``, the range checks
#: of the narrowing casts)
_NULLS_ESCAPED_LANES = frozenset({
    "add", "sub", "mul", "neg", "abs", "cast_smallint", "cast_tinyint"})


def _report_hazards(e: Call, args, arg_types, raw: AbstractValue,
                    on_hazard) -> None:
    fn = e.fn
    if fn in ("add", "sub", "mul", "neg", "abs"):
        dev = device_int_bounds(e.type)
        if dev is not None and (raw.lo < dev[0] or raw.hi > dev[1]):
            on_hazard("overflow", e, (raw.lo, raw.hi), dev,
                      known=raw.known)
    elif fn in ("div", "mod") and e.type.name not in ("double", "real"):
        b = args[1]
        if b.lo <= 0 <= b.hi:
            on_hazard("division", e, (b.lo, b.hi), (0, 0),
                      known=b.known and b.lo == b.hi == 0)
    elif fn in ("cast_bigint", "cast_smallint", "cast_tinyint",
                "cast_decimal"):
        t0 = arg_types[0]
        if t0.is_string or t0.name in ("double", "real"):
            return
        a = args[0]
        lo, hi = _rescale_iv(a.lo, a.hi, _scale_of(t0),
                             _scale_of(e.type))
        if fn != "cast_decimal":
            lo, hi = lo - 1, hi + 1  # rounding slack
        tb = type_bounds(e.type)
        if lo < tb[0] or hi > tb[1]:
            on_hazard("lossy-cast", e, (lo, hi), tb, known=a.known)


def channel_value_of_channel(ch) -> AbstractValue:
    """Abstract value of one plan-node output channel (planner.plan
    Channel): zone-map domain when present, else the type contract."""
    t = ch.type
    if getattr(ch, "domain", None) is not None and t.value_shape == () \
            and not t.is_raw_string:
        return from_channel(t, ch.domain)
    return top(t)


# ---------------------------------------------------------------------------
# proofs for code generation
# ---------------------------------------------------------------------------
# The same intervals license the cheapest exact form of the generated
# arithmetic (expr/compile.py ``_compile_arith``, ops/aggregate.py
# ``_partial_states``).  A proof holds for every lane that is live and
# non-NULL: a dead or NULL lane may hold anything (a row the scan's
# pushed-down conjunct will mask, a wrapped product under a guard that
# fired), and nothing reads its data.  Only ``known`` intervals prove
# (literals, connector domains, VALUES rows): the type contract alone
# never drops a guard.

#: the kernels whose compiled form carries a runtime guard an interval
#: can discharge: the wrap mask of add/sub/mul/neg on integer lanes
#: (with the ``_rescale_guard`` of a decimal operand), the zero check
#: of div/mod
GUARDED_FNS = frozenset({"add", "sub", "mul", "neg", "div", "mod"})


def arith_sites(exprs: Sequence[Optional[Expr]]) -> Iterator[Call]:
    """The guarded arithmetic calls of ``exprs`` in pre-order, each
    expression in turn: THE order of a stage's ``proven`` tuple.
    Lambda bodies are not entered (their lanes have no interval), long
    decimals and the float forms of add/sub/mul carry no guard."""
    for root in exprs:
        stack = [root]
        while stack:
            e = stack.pop()
            if not isinstance(e, Call):
                continue
            if is_guarded(e):
                yield e
            stack.extend(reversed(e.args))


def is_guarded(e: Call) -> bool:
    if e.fn not in GUARDED_FNS or e.type.is_long_decimal:
        return False
    if e.fn in ("div", "mod"):
        return e.type.value_shape == ()
    return device_int_bounds(e.type) is not None


def _inside(lo, hi, bounds) -> bool:
    return bounds[0] <= lo and hi <= bounds[1]


def site_proven(e: Call, env: Sequence[AbstractValue],
                memo: Optional[Dict[int, AbstractValue]] = None) -> bool:
    """True when no guard of the guarded call ``e`` can fire over
    ``env``: the raw result of add/sub/mul/neg (and each up-scaled
    decimal operand) stays inside the lane, the divisor of div/mod
    excludes zero (and ``INT_MIN / -1`` cannot meet)."""
    if memo is None:
        memo = {}
    args = [eval_expr(a, env, None, memo) for a in e.args]
    if not all(a.known for a in args):
        return False
    arg_types = [a.type for a in e.args]
    if not e.type.is_decimal and device_int_bounds(e.type) is not None \
            and np.result_type(*(t.np_dtype for t in arg_types)) \
            != e.type.np_dtype:
        # the integer kernels compute in the promotion of their
        # operands' lanes; the proof is of the declared lane
        return False
    so = _scale_of(e.type)
    if e.type.is_decimal and e.fn in ("add", "sub", "mod"):
        # operands are up-scaled to the result's scale first
        for a, t in zip(args, arg_types):
            if not _inside(*_rescale_iv(a.lo, a.hi, _scale_of(t), so), I64):
                return False
    if e.fn in ("div", "mod"):
        a, b = args
        if b.lo <= 0 <= b.hi:
            return False
        dev = device_int_bounds(e.type)
        if e.fn == "div" and dev is not None and not e.type.is_decimal:
            return not (a.lo <= dev[0] and b.lo <= -1 <= b.hi)
        return True
    raw = transfer(e.fn, e.type, args, arg_types)
    return raw.known and _inside(raw.lo, raw.hi, device_int_bounds(e.type))


def prove_sites(exprs: Sequence[Optional[Expr]],
                env: Sequence[AbstractValue]) -> Tuple[bool, ...]:
    """One outcome per :func:`arith_sites` site of ``exprs`` over the
    channel values ``env``: True = compiled without its guard."""
    memo: Dict[int, AbstractValue] = {}
    return tuple(site_proven(e, env, memo) for e in arith_sites(exprs))


def proven_table(exprs: Sequence[Optional[Expr]],
                 proven: Sequence[bool]) -> Dict[int, bool]:
    """``id(site) -> outcome`` for the compiler, from a stage's
    expressions and the ``proven`` tuple its lowering signed.  A tuple
    of another length than the sites (no proof was made) proves
    nothing."""
    sites = list(arith_sites(exprs))
    if len(sites) != len(proven):
        return {}
    return {id(e): bool(ok) for e, ok in zip(sites, proven)}


#: a page has at most this many rows (row numbers are int32)
MAX_PAGE_ROWS = 1 << 31
#: a proof that holds only for pages under this many rows is not taken
MIN_LANE_ROWS = 1 << 10


def sum_lane_rows(v: AbstractValue) -> int:
    """The page capacity up to which the sum of an int64-lane addend
    with value ``v`` cannot wrap one int64 lane: the largest power of
    two ``r <= MAX_PAGE_ROWS`` with ``max(|lo|, |hi|) * r`` inside
    int64, or 0 (no proof).  A power of two so that domains that differ
    and prove the same sign the same program."""
    if not v.known:
        return 0
    m = max(abs(v.lo), abs(v.hi))
    if m == INF:
        return 0
    r = MAX_PAGE_ROWS
    while r >= MIN_LANE_ROWS and m * r > I64[1]:
        r >>= 1
    return r if r >= MIN_LANE_ROWS else 0
