"""Grouped aggregation kernels.

Reference analog: HashAggregationOperator
(operator/HashAggregationOperator.java:46) with GroupByHash
(operator/MultiChannelGroupByHash.java:54 — open-addressing row hash)
and the JIT-compiled accumulators (operator/aggregation/,
AccumulatorCompiler.java). Open-addressing probes are scalar-serial and
hostile to the TPU's vector units, so group resolution is re-designed:

* **Packed-direct path**: when every group key has a known small domain
  (dictionary codes, flags, small ints), the packed key IS the group id
  — no sort, one `segment_sum` per aggregate. This is the TPC-H Q1
  shape (6 groups) and the analog of the reference's
  BigintGroupByHash specialization.

* **Sort path**: general case. Pack (exact, when domains fit in 63
  bits) or hash-mix the key columns into one int64 and sort it once
  with every aggregate's inputs riding along (from three lanes on as
  the rows of one int64 stack, ``_sorted_runs``), in as many sorts of
  the same key as the page's size allows (``_batches``); group ids
  come from sorted-run boundaries and the segment reductions walk the
  sorted order, so no row is gathered or scattered back.
  Deterministic output order (sorted by packed/hashed key).

Aggregates are expressed as (state columns, merge, finalize) triples so
the same kernel serves single-node, partial (pre-exchange) and final
(post-exchange) aggregation — the PARTIAL/FINAL split of
iterative/rule/PushPartialAggregationThroughExchange.java.

Exact sums: DECIMAL aggregates accumulate in scaled int64 when the
argument precision is at most SUM_SHORT_SAFE_PRECISION (15); higher
short precisions — every decimal arithmetic product types as p=18 —
accumulate in two-limb decimal128 state instead, because an int64
accumulator wraps silently once |addend| * rows crosses 2^63 (the
SF100 Q1 sum_charge class: ~6e9 rows x 10^16-scale addends; the
reference's checked accumulators raise ARITHMETIC_OVERFLOW there).
The limb fold (decimal128.to_sum_limbs) is exact to ~9.2e9 addends;
the kernel-soundness analyzer (analysis/kernel_soundness.py) flags any
accumulator whose folded interval still escapes its state width.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.expr.compile import ExprCompiler, proven_sites
from presto_tpu.expr.ir import AggCall, Expr
from presto_tpu.page import Block, Page
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR, DecimalType, Type

_I64_MAX = jnp.iinfo(jnp.int64).max

AggSpec = AggCall  # public alias

DIRECT_GROUP_LIMIT = 1 << 14

# HyperLogLog bucket count (2^p, p=12 — the reference's default
# approx_distinct standard error 2.3%/sqrt-law class); must match
# ExprCompiler.HLL_P in expr/compile.py
HLL_M = 1 << 12

# static per-group element capacity of array_agg (the reference's
# ArrayAggregationFunction is unbounded; a fixed slot count keeps the
# state a dense (groups, cap) matrix — results past the cap truncate)
ARRAY_AGG_CAP = 64

# class-count cap of learn_classifier (labels must be ints in [0, C));
# reference presto-ml trains libsvm models — here Gaussian naive Bayes,
# whose sufficient statistics are plain segment sums (TPU-native)
ML_MAX_CLASSES = 8


# ---------------------------------------------------------------------------
# agg state machinery
# ---------------------------------------------------------------------------

# max short-decimal argument precision whose sum may accumulate in a
# plain int64 lane: 10^15 * ~9.2e3 max rows-per-... — conservatively,
# |addend| <= 10^15 leaves four orders of magnitude of headroom below
# 2^63 (~9.2e18), i.e. the fold stays exact past 9000x the largest
# tier-1 table; p=16..18 addends (every decimal arith product types as
# p=18) can cross 2^63 at realistic SF100 row counts and widen to
# two-limb decimal128 accumulation instead
SUM_SHORT_SAFE_PRECISION = 15


def _sum_type(t: Type) -> Type:
    if t.is_decimal:
        if (t.precision or 0) > 36:
            return DecimalType(38, t.scale)
        if t.is_long_decimal or (t.precision or 0) > SUM_SHORT_SAFE_PRECISION:
            return DecimalType(36, t.scale)
        return DecimalType(18, t.scale)
    if t.name.startswith("interval"):
        return t  # interval sums stay interval (Interval*SumAggregation)
    if t.name in ("double", "real"):
        return DOUBLE  # REAL accumulates in double (reference parity)
    return BIGINT  # tinyint/smallint/integer/bigint widen to bigint


VARIANCE_FNS = ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop")
# higher central moments (CentralMomentsAggregation: skewness/kurtosis)
MOMENT_FNS = ("skewness", "kurtosis")
# bitwise folds (BitwiseAndAggregation / BitwiseOrAggregation)
BITWISE_FNS = ("bitwise_and_agg", "bitwise_or_agg")

# two-argument moment statistics (AggregationUtils covariance/corr/
# regression states): fn(y, x) with state (sx, sy, sxy, sxx, syy, n)
COVAR_FNS = ("covar_pop", "covar_samp", "corr", "regr_slope", "regr_intercept")


def state_types(agg: AggCall) -> List[Type]:
    """Column types of this aggregate's partial state."""
    if agg.fn == "count_star" or agg.fn == "count":
        return [BIGINT]
    t = agg.arg.type
    if agg.fn in ("sum", "sum0"):
        return [_sum_type(t), BIGINT]
    if agg.fn == "avg":
        return [_sum_type(t), BIGINT]
    if agg.fn in ("min", "max"):
        return [t, BIGINT]
    if agg.fn in VARIANCE_FNS:
        return [DOUBLE, DOUBLE, BIGINT]  # sum, M2 (Σ(x-mean)²), count
    if agg.fn in MOMENT_FNS:
        return [DOUBLE, DOUBLE, DOUBLE, DOUBLE, BIGINT]  # s, M2, M3, M4, n
    if agg.fn in BITWISE_FNS:
        return [BIGINT, BIGINT]  # folded value, count of non-null
    if agg.fn in ("bool_and", "bool_or", "every"):
        return [BIGINT, BIGINT]  # count of true, count of non-null
    if agg.fn in COVAR_FNS:
        return [DOUBLE, DOUBLE, DOUBLE, DOUBLE, DOUBLE, BIGINT]
    if agg.fn == "checksum":
        return [BIGINT]
    if agg.fn in ("min_by", "max_by"):
        # x-at-extreme, x-non-null flag, extreme key, count of valid keys
        return [t, BIGINT, agg.arg2.type, BIGINT]
    if agg.fn == "hll_merge":
        # HyperLogLog register fold: Σ 2^-M over present buckets, count
        # of present buckets (input rows are one-per-(group, bucket))
        return [DOUBLE, BIGINT]
    if agg.fn == "array_agg":
        from presto_tpu.types import ArrayType

        return [ArrayType(t, ARRAY_AGG_CAP), BIGINT]
    if agg.fn in ("map_agg", "multimap_agg"):
        from presto_tpu.types import MapType

        return [MapType(t, agg.arg2.type, ARRAY_AGG_CAP), BIGINT]
    if agg.fn == "map_union":
        from presto_tpu.types import MapType

        return [MapType(t.key_element, t.element, ARRAY_AGG_CAP), BIGINT]
    if agg.fn in ("max_n", "min_n"):
        from presto_tpu.types import ArrayType

        return [ArrayType(t, int(agg.arg2.value)), BIGINT]
    if agg.fn in ("max_by_n", "min_by_n"):
        # two value halves sharing one storage dtype: the map state
        # geometry [len, xs.., ys..] with ys = the ordering keys, so
        # partial states merge exactly (top-n is a semilattice)
        from presto_tpu.types import MapType

        return [MapType(t, agg.arg2.type, int(agg.arg3.value)), BIGINT]
    if agg.fn == "hll_sketch":
        from presto_tpu.types import HllType

        return [HllType(), BIGINT]
    if agg.fn in ("make_set_digest", "merge_set_digest"):
        from presto_tpu.types import SetDigestType

        return [SetDigestType(), BIGINT]
    if agg.fn == "learn_regressor":
        # normal-equation sufficient statistics: flattened upper
        # triangle-free full XtX (dim*dim) + Xty (dim), dim = k+1 bias
        from presto_tpu.types import ArrayType

        dim = agg.arg2.type.max_elems + 1
        return [ArrayType(DOUBLE, dim * dim + dim), BIGINT]
    if agg.fn == "learn_classifier":
        # per class: count, sum x_j, sum x_j^2  (Gaussian NB stats)
        from presto_tpu.types import ArrayType

        k = agg.arg2.type.max_elems
        return [ArrayType(DOUBLE, ML_MAX_CLASSES * (1 + 2 * k)), BIGINT]
    if agg.fn == "evaluate_classifier_predictions":
        # per class: [tp, fp, fn] counts (presto-ml
        # EvaluateClassifierPredictionsAggregation state maps)
        from presto_tpu.types import ArrayType

        return [ArrayType(BIGINT, 3 * ML_MAX_CLASSES), BIGINT]
    raise KeyError(f"unknown aggregate {agg.fn}")


def output_type(agg: AggCall) -> Type:
    if agg.fn in ("count", "count_star", "hll_merge", "approx_distinct"):
        return BIGINT
    if agg.fn == "approx_set":
        from presto_tpu.types import HllType

        return HllType()  # rewritten to the two-level sketch pipeline
    if agg.fn == "merge":
        return agg.arg.type  # hll in, hll out (rewritten before exec)
    if agg.fn == "array_agg":
        from presto_tpu.types import ArrayType

        return ArrayType(agg.arg.type, ARRAY_AGG_CAP)
    if agg.fn == "map_agg":
        from presto_tpu.types import MapType

        return MapType(agg.arg.type, agg.arg2.type, ARRAY_AGG_CAP)
    if agg.fn == "hll_sketch":
        from presto_tpu.types import HllType

        return HllType()
    if agg.fn in ("make_set_digest", "merge_set_digest"):
        from presto_tpu.types import SetDigestType

        return SetDigestType()
    if agg.fn == "evaluate_classifier_predictions":
        # VARCHAR summary; the numeric state travels through the jitted
        # pipeline and LocalRunner formats it host-side at the end
        return VARCHAR
    if agg.fn == "multimap_agg":
        from presto_tpu.types import ArrayType, MapType

        vt = agg.arg2.type
        if not vt.is_array:  # pre-rewrite: second arg is the scalar v
            vt = ArrayType(vt, ARRAY_AGG_CAP)
        return MapType(agg.arg.type, vt, ARRAY_AGG_CAP)
    if agg.fn == "map_union":
        from presto_tpu.types import MapType

        t = agg.arg.type
        return MapType(t.key_element, t.element, ARRAY_AGG_CAP)
    if agg.fn in ("max_n", "min_n"):
        from presto_tpu.types import ArrayType

        return ArrayType(agg.arg.type, int(agg.arg2.value))
    if agg.fn in ("max_by_n", "min_by_n"):
        from presto_tpu.types import ArrayType

        return ArrayType(agg.arg.type, int(agg.arg3.value))
    if agg.fn == "histogram":
        # rewritten to inner count + outer map_agg before execution
        from presto_tpu.types import MapType

        return MapType(agg.arg.type, BIGINT, ARRAY_AGG_CAP)
    if agg.fn == "numeric_histogram":
        # rewritten to window-span bins + map_agg before execution;
        # the map width is the shared container cap so the rewrite's
        # map_agg state layout and this declared type agree
        from presto_tpu.types import MapType

        return MapType(DOUBLE, DOUBLE, ARRAY_AGG_CAP)
    if agg.fn == "learn_regressor":
        from presto_tpu.types import ArrayType

        return ArrayType(DOUBLE, agg.arg2.type.max_elems + 1)
    if agg.fn == "learn_classifier":
        from presto_tpu.types import ArrayType

        k = agg.arg2.type.max_elems
        return ArrayType(DOUBLE, 1 + ML_MAX_CLASSES * (1 + 2 * k))
    if agg.fn in ("sum", "sum0"):
        return _sum_type(agg.arg.type)
    if agg.fn == "avg":
        if agg.arg.type.is_decimal:
            # reference parity: avg(decimal(p,s)) keeps the input type,
            # rounded HALF_UP at scale s (DecimalAverageAggregation)
            return agg.arg.type
        if agg.arg.type.name.startswith("interval"):
            return agg.arg.type  # Interval*AverageAggregation
        return DOUBLE
    if agg.fn in VARIANCE_FNS or agg.fn in COVAR_FNS or agg.fn in MOMENT_FNS:
        return DOUBLE
    if agg.fn in BITWISE_FNS:
        return BIGINT
    if agg.fn == "checksum":
        return BIGINT
    if agg.fn in ("bool_and", "bool_or", "every"):
        from presto_tpu.types import BOOLEAN

        return BOOLEAN
    return agg.arg.type  # min/max/min_by/max_by/approx_percentile: x's type


# Below this segment count, segment reductions lower to a fused masked
# broadcast-reduce instead of XLA's scatter-add — scatter serializes on
# the TPU (measured 583ms vs ~0ms extra for a 6M-row f64 page), while
# the masked form fuses into one memory pass per call.  XLA:CPU does
# NOT fuse the broadcast (it materializes the (G, rows) intermediate,
# measured 10x slower on TPC-H Q1) and its scatter-add is fine, so the
# masked form is TPU-only.
SMALL_SEG_LIMIT = 128


def _masked_segments_profitable() -> bool:
    import jax as _jax

    return _jax.default_backend() != "cpu"


def _seg_sum(vals, gid, n):
    if n <= SMALL_SEG_LIMIT and _masked_segments_profitable():
        seg = jnp.arange(n, dtype=gid.dtype)
        hit = gid[None, :] == seg[:, None]
        if vals.ndim == 1:
            return jnp.sum(jnp.where(hit, vals[None, :], jnp.zeros_like(vals)[None, :]), axis=1)
        # leading-axis segmentation of (rows, k) limb arrays
        return jnp.sum(
            jnp.where(hit[:, :, None], vals[None, :, :], jnp.zeros_like(vals)[None, :, :]),
            axis=1,
        )
    return jax.ops.segment_sum(vals, gid, num_segments=n)


def _gsum(ctx, vals, gid, n):
    """Per-group sums for groups 0..n-1 (rows with gid == n are dead):
    cumsum-over-sorted-runs when a _SortCtx is available and the group
    count is past the masked-reduce limit, else _seg_sum."""
    if ctx is not None and n + 1 > SMALL_SEG_LIMIT:
        return ctx.sum(vals, gid, n)
    return _seg_sum(vals, gid, n + 1)[:n]


def _ident_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).max
    if dtype == jnp.bool_:
        return True
    return jnp.iinfo(dtype).max


def _ident_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).min
    if dtype == jnp.bool_:
        return False
    return jnp.iinfo(dtype).min


def _seg_min(vals, gid, n):
    if n <= SMALL_SEG_LIMIT and _masked_segments_profitable():
        seg = jnp.arange(n, dtype=gid.dtype)
        hit = gid[None, :] == seg[:, None]
        fill = jnp.asarray(_ident_max(vals.dtype), vals.dtype)
        return jnp.min(jnp.where(hit, vals[None, :], fill), axis=1)
    return jax.ops.segment_min(vals, gid, num_segments=n)


def _seg_max(vals, gid, n):
    if n <= SMALL_SEG_LIMIT and _masked_segments_profitable():
        seg = jnp.arange(n, dtype=gid.dtype)
        hit = gid[None, :] == seg[:, None]
        fill = jnp.asarray(_ident_min(vals.dtype), vals.dtype)
        return jnp.max(jnp.where(hit, vals[None, :], fill), axis=1)
    return jax.ops.segment_max(vals, gid, num_segments=n)


def _seg_assoc(op, identity, vals, gid, n):
    """Segmented reduction under ANY associative op (bitwise and/or
    here): argsort rows by group, run one segmented
    ``associative_scan`` (scan state = (segment-start flag, value); a
    start flag resets the accumulation), then gather each group's last
    scan position via searchsorted — no scatter, TPU-friendly.  Rows
    with gid == n are dead and land in the trailing run."""
    order = jnp.argsort(gid)
    g = gid[order]
    v = vals[order]
    starts = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), g[1:] != g[:-1]])

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))

    _, scanned = jax.lax.associative_scan(combine, (starts, v))
    # g is sorted: each group's last row index via right-edge search;
    # a group is present exactly when the row at its right edge still
    # carries its id
    ends = jnp.clip(jnp.searchsorted(g, jnp.arange(n, dtype=g.dtype),
                                     side="right") - 1, 0, g.shape[0] - 1)
    present = g[ends] == jnp.arange(n, dtype=g.dtype)
    return jnp.where(present, scanned[ends], identity)


def agg_exprs(group_exprs: Sequence[Expr],
              aggs: Sequence[AggCall]) -> List[Optional[Expr]]:
    """Every expression a (partial) aggregation compiles, in THE order
    its ``proven`` outcomes are signed in (``analysis.ranges.
    arith_sites`` walks them): the keys, then each aggregate's
    arguments and filter."""
    out: List[Optional[Expr]] = list(group_exprs)
    for a in aggs:
        out += [a.arg, a.arg2, a.arg3, a.filter]
    return out


def limb_sum_site(agg: AggCall) -> bool:
    """True for the sums a proof can move: a short (int64-lane) addend
    whose state is limbs, split row by row unless its interval says the
    page's sum fits one lane (``_partial_states``)."""
    return (agg.fn in ("sum", "sum0", "avg") and agg.arg is not None
            and not agg.arg.type.is_long_decimal
            and _sum_type(agg.arg.type).is_long_decimal)


def one_lane_sums(aggs: Sequence[AggCall],
                  lane_rows: Optional[Sequence[Optional[int]]],
                  n_groups: int,
                  capacity: Optional[int] = None) -> Tuple[bool, ...]:
    """Per aggregate, whether its limb sum reduces in ONE int64 lane
    and lifts the group sums to limbs afterwards, instead of splitting
    every row: THE rule, for ``grouped_aggregate`` and for whoever
    counts its outcome (``exec/chain.Chain.arith_counts``).

    ``lane_rows[i]`` is what the plan's intervals proved of aggregate
    ``i``'s addend: the page capacity (``analysis.ranges.
    sum_lane_rows``) up to which the page's sum stays inside int64; 0
    or None = no proof.  One lane where that covers the page
    (``capacity``; None: any page the proof covers) and the groups are
    few enough for the masked reduce, where it was measured (PERF.md,
    PR 36: 16-20 ms a 2^23-row page saved).  Over sorted runs
    (``_SortCtx.sum``) the one-lane form compiled to more gather
    fusions than the limbs and q3 lost 4 ms a page: limbs there."""
    if not lane_rows or n_groups + 1 > SMALL_SEG_LIMIT:
        return (False,) * len(aggs)
    return tuple(
        bool(limb_sum_site(a) and r
             and (capacity is None or capacity <= r))
        for a, r in zip(aggs, lane_rows))


@jax.named_scope("agg:reduce")
def _partial_states(page: Page, aggs: Sequence[AggCall], gid: jax.Array, n: int,
                    ctx: "Optional[_SortCtx]" = None,
                    c: Optional[ExprCompiler] = None,
                    one_lane: Sequence[bool] = ()):
    """Compute per-group state columns for each aggregate.

    gid must already be ``n`` for dead rows (dropped by segment ops via
    an extra slot).  ``one_lane[i]``: aggregate ``i``'s limb sum is
    proven to fit one int64 lane over this page (``one_lane_sums``);
    absent: limbs row by row."""
    if c is None:
        c = ExprCompiler.for_page(page)
    out: List[List[jax.Array]] = []
    live = page.row_mask
    for i, agg in enumerate(aggs):
        if agg.filter is not None:
            fd, fv = c.compile(agg.filter)(page)
            rowsel = live & fd & fv
        else:
            rowsel = live
        gid_a = jnp.where(rowsel, gid, n)
        if agg.fn == "count_star":
            cnt = _gsum(ctx, jnp.ones_like(gid_a, dtype=jnp.int64), gid_a, n)
            out.append([cnt])
            continue
        data, valid = c.compile(agg.arg)(page)
        if agg.fn in ("min", "max") and agg.arg.type.is_raw_string:
            # raw varchar: k-phase lexicographic reduction over
            # order-preserving int64 lanes (PagesIndex VARCHAR
            # comparator role, no scalar loops)
            from presto_tpu.ops import rawstring as rs

            nonnull = rowsel & valid
            gid_nn = jnp.where(nonnull, gid, n)
            cnt = _gsum(ctx, nonnull.astype(jnp.int64), gid_nn, n)
            lanes = rs.pack_lanes(data)
            best = _minmax_lanes(agg.fn, lanes, nonnull, gid_nn, n)
            out.append([rs.unpack_lanes(best, data.shape[-1]), cnt])
            continue
        if agg.fn in ("min", "max") and agg.arg.type.is_string:
            # reduce over collation ranks, not assignment-ordered codes
            adict = _agg_dict(agg, [b.dictionary for b in page.blocks])
            if adict is not None:
                rank_lut, _ = _collation_luts(adict)
                data = rank_lut[jnp.clip(data, 0, rank_lut.shape[0] - 1)]
        nonnull = rowsel & valid
        gid_nn = jnp.where(nonnull, gid, n)
        cnt = _gsum(ctx, nonnull.astype(jnp.int64), gid_nn, n)
        if agg.fn == "count":
            out.append([cnt])
        elif agg.fn in ("sum", "sum0", "avg") \
                and _sum_type(agg.arg.type).is_long_decimal:
            from presto_tpu.ops import decimal128 as d128

            if one_lane and one_lane[i]:
                # |addend| * capacity is proven inside int64: reduce in
                # one lane and lift the n group sums to the limb state,
                # not every row (the same integer, the same canonical
                # limbs)
                vals = jnp.where(nonnull, data.astype(jnp.int64), 0)
                s = d128.from_int64(_gsum(ctx, vals, gid_nn, n))
                out.append([s, cnt])
                continue
            # covers short p>15 args too: their scaled-int64 lanes lift
            # to two-limb rows first, then the same base-1e9 digit fold
            if not agg.arg.type.is_long_decimal:
                data = d128.from_int64(data.astype(jnp.int64))
            limbs = d128.to_sum_limbs(data)
            limbs = jnp.where(nonnull[:, None], limbs, 0)
            s = d128.from_sum_limbs(_gsum(ctx, limbs, gid_nn, n))
            out.append([s, cnt])
        elif agg.fn in ("sum", "sum0", "avg"):
            st = _sum_type(agg.arg.type)
            vals = data.astype(st.np_dtype)
            vals = jnp.where(nonnull, vals, jnp.zeros_like(vals))
            s = _gsum(ctx, vals, gid_nn, n)
            out.append([s, cnt])
        elif agg.fn in ("min", "max") and agg.arg.type.is_long_decimal:
            out.append(_minmax_long(agg.fn, data, nonnull, gid_nn, n) + [cnt])
        elif agg.fn in ("min", "max"):
            if agg.fn == "min":
                fill = _type_max(agg.arg.type)
                m = _seg_min(
                    jnp.where(nonnull, data, fill), gid_nn, n + 1
                )[:n]
            else:
                fill = _type_min(agg.arg.type)
                m = _seg_max(
                    jnp.where(nonnull, data, fill), gid_nn, n + 1
                )[:n]
            out.append([m, cnt])
        elif agg.fn in VARIANCE_FNS:
            from presto_tpu.expr.compile import _to_double

            # Welford-style state (count, mean, M2) per the reference's
            # AggregationUtils.updateVarianceState — s2/n - mean² loses
            # all precision when |mean| >> stddev.  Two passes: segment
            # mean first, then mean-relative second moment.
            x = jnp.where(nonnull, _to_double(data, agg.arg.type), 0.0)
            s = _gsum(ctx, x, gid_nn, n)
            mu = s / jnp.maximum(cnt, 1).astype(jnp.float64)
            mu_row = mu[jnp.clip(gid_nn, 0, n - 1)]
            dx = jnp.where(nonnull, x - mu_row, 0.0)
            m2 = _gsum(ctx, dx * dx, gid_nn, n)
            out.append([s, m2, cnt])
        elif agg.fn in MOMENT_FNS:
            from presto_tpu.expr.compile import _to_double

            # two-pass central moments, like the variance state
            x = jnp.where(nonnull, _to_double(data, agg.arg.type), 0.0)
            s = _gsum(ctx, x, gid_nn, n)
            mu = s / jnp.maximum(cnt, 1).astype(jnp.float64)
            dx = jnp.where(nonnull, x - mu[jnp.clip(gid_nn, 0, n - 1)], 0.0)
            dx2 = dx * dx
            out.append([s, _gsum(ctx, dx2, gid_nn, n),
                        _gsum(ctx, dx2 * dx, gid_nn, n),
                        _gsum(ctx, dx2 * dx2, gid_nn, n), cnt])
        elif agg.fn in BITWISE_FNS:
            is_and = agg.fn == "bitwise_and_agg"
            ident = jnp.int64(-1) if is_and else jnp.int64(0)
            v = jnp.where(nonnull, data.astype(jnp.int64), ident)
            op = jnp.bitwise_and if is_and else jnp.bitwise_or
            out.append([_seg_assoc(op, ident, v, gid_nn, n), cnt])
        elif agg.fn in ("bool_and", "bool_or", "every"):
            t = _seg_sum((nonnull & data.astype(jnp.bool_)).astype(jnp.int64),
                         gid_nn, n + 1)[:n]
            out.append([t, cnt])
        elif agg.fn in COVAR_FNS:
            from presto_tpu.expr.compile import _to_double

            x_data, x_valid = c.compile(agg.arg2)(page)
            sel = rowsel & valid & x_valid
            gid_s = jnp.where(sel, gid, n)
            y = jnp.where(sel, _to_double(data, agg.arg.type), 0.0)
            x = jnp.where(sel, _to_double(x_data, agg.arg2.type), 0.0)
            out.append([
                _gsum(ctx, x, gid_s, n),
                _gsum(ctx, y, gid_s, n),
                _gsum(ctx, x * y, gid_s, n),
                _gsum(ctx, x * x, gid_s, n),
                _gsum(ctx, y * y, gid_s, n),
                _gsum(ctx, sel.astype(jnp.int64), gid_s, n),
            ])
        elif agg.fn == "checksum":
            # order-independent wrapping sum of per-value hashes
            # (CheckSumAggregation — the verifier's result digest)
            if jnp.issubdtype(data.dtype, jnp.floating):
                lane = jax.lax.bitcast_convert_type(
                    data.astype(jnp.float64), jnp.int64)
            elif data.ndim > 1:
                from presto_tpu.ops.rawstring import hash_bytes

                lane = (hash_bytes(data.astype(jnp.uint8))
                        if data.dtype == jnp.uint8
                        else data[..., 0] * jnp.int64(1000003) + data[..., 1])
            else:
                lane = data.astype(jnp.int64)
            h = _mix64(lane.astype(jnp.uint64)).astype(jnp.int64)
            h = jnp.where(valid, h, jnp.int64(0x9E3779B97F4A7C15 - 2 ** 64))
            h = jnp.where(rowsel, h, 0)
            out.append([_gsum(ctx, h, jnp.where(rowsel, gid, n), n)])
        elif agg.fn in ("min_by", "max_by"):
            # two-phase coupled reduction: per-group extreme of the key,
            # then (any) x among the rows achieving it (reference:
            # operator/aggregation/minmaxby/ MinMaxByStateFactory)
            if agg.arg.type.value_shape or agg.arg2.type.value_shape:
                raise ValueError(
                    f"{agg.fn} over raw varchar / long decimal unsupported")
            y_data, y_valid = c.compile(agg.arg2)(page)
            if agg.arg2.type.is_string:
                from presto_tpu.expr.compile import expr_dictionary

                ydict = expr_dictionary(agg.arg2, [b.dictionary for b in page.blocks])
                if ydict is not None:
                    y_rank, _ = _collation_luts(ydict)
                    y_data = y_rank[jnp.clip(y_data, 0, y_rank.shape[0] - 1)]
            sel = rowsel & y_valid
            gid_y = jnp.where(sel, gid, n)
            ycnt = _gsum(ctx, sel.astype(jnp.int64), gid_y, n)
            if agg.fn == "min_by":
                yfill = _type_max(agg.arg2.type)
                y_best = _seg_min(
                    jnp.where(sel, y_data, yfill), gid_y, n + 1)[:n]
            else:
                yfill = _type_min(agg.arg2.type)
                y_best = _seg_max(
                    jnp.where(sel, y_data, yfill), gid_y, n + 1)[:n]
            tie = sel & (y_data == y_best[jnp.clip(gid_y, 0, n - 1)])
            xv = tie & valid
            x_best = _seg_max(
                jnp.where(xv, data, _type_min(agg.arg.type)),
                jnp.where(xv, gid, n), n + 1)[:n]
            xv_cnt = _gsum(ctx, xv.astype(jnp.int64), jnp.where(xv, gid, n), n)
            out.append([x_best, (xv_cnt > 0).astype(jnp.int64), y_best, ycnt])
        elif agg.fn == "hll_merge":
            # fold rho rows (one per (group, bucket)) into the sketch sum
            rho = jnp.where(nonnull, data.astype(jnp.float64), 0.0)
            s = _seg_sum(jnp.where(nonnull, jnp.exp2(-rho), 0.0), gid_nn, n + 1)[:n]
            out.append([s, cnt])
        elif agg.fn == "evaluate_classifier_predictions":
            # per-class tp/fp/fn lanes summed per group (presto-ml
            # EvaluateClassifierPredictionsAggregation input/combine;
            # labels are class ids in [0, ML_MAX_CLASSES))
            C = ML_MAX_CLASSES
            p_data, p_valid = c.compile(agg.arg2)(page)
            t64 = data.astype(jnp.int64)
            p64 = p_data.astype(jnp.int64)
            sel = (rowsel & valid & p_valid
                   & (t64 >= 0) & (t64 < C) & (p64 >= 0) & (p64 < C))
            cls = jnp.arange(C, dtype=jnp.int64)[None, :]
            t_oh = t64[:, None] == cls
            p_oh = p64[:, None] == cls
            eq = (t64 == p64)[:, None]
            lanes = jnp.concatenate(
                [jnp.where(t_oh & eq, 1, 0),          # tp at truth cls
                 jnp.where(p_oh & ~eq, 1, 0),         # fp at pred cls
                 jnp.where(t_oh & ~eq, 1, 0)],        # fn at truth cls
                axis=1).astype(jnp.int64)
            lanes = jnp.where(sel[:, None], lanes, 0)
            gid_s = jnp.where(sel, gid, n)
            scnt = _gsum(ctx, sel.astype(jnp.int64), gid_s, n)
            sums = _gsum(ctx, lanes, gid_s, n)
            state = jnp.concatenate(
                [jnp.full((n, 1), 3 * C, dtype=jnp.int64), sums], axis=1)
            out.append([state, scnt])
        elif agg.fn in ("learn_regressor", "learn_classifier"):
            # sufficient statistics are segment sums (TPU-native
            # training): normal equations for the regressor, Gaussian
            # NB class stats for the classifier (presto-ml analog)
            from presto_tpu.ops import container as ct

            ft = agg.arg2.type
            f_data, f_valid = c.compile(agg.arg2)(page)
            k = ft.max_elems
            slots = ct.elem_slots(f_data, ft)
            feats = jnp.where(ct.elem_null_mask(slots), 0.0,
                              slots.astype(jnp.float64))
            sel = rowsel & valid & f_valid
            gid_s = jnp.where(sel, gid, n)
            scnt = _gsum(ctx, sel.astype(jnp.int64), gid_s, n)
            if agg.fn == "learn_regressor":
                from presto_tpu.expr.compile import _to_double

                y = jnp.where(sel, _to_double(data, agg.arg.type), 0.0)
                x_aug = jnp.concatenate(
                    [feats, jnp.ones((feats.shape[0], 1))], axis=1)
                dim = k + 1
                outer = (x_aug[:, :, None] * x_aug[:, None, :]).reshape(
                    feats.shape[0], dim * dim)
                lanes = jnp.concatenate([outer, x_aug * y[:, None]], axis=1)
            else:
                cls = jnp.clip(data.astype(jnp.int64), 0, ML_MAX_CLASSES - 1)
                onehot = (cls[:, None] == jnp.arange(ML_MAX_CLASSES)[None, :]
                          ).astype(jnp.float64)
                sumx = (onehot[:, :, None] * feats[:, None, :]).reshape(
                    feats.shape[0], ML_MAX_CLASSES * k)
                sumx2 = (onehot[:, :, None] * (feats ** 2)[:, None, :]).reshape(
                    feats.shape[0], ML_MAX_CLASSES * k)
                lanes = jnp.concatenate([onehot, sumx, sumx2], axis=1)
            lanes = jnp.where(sel[:, None], lanes, 0.0)
            s = _gsum(ctx, lanes, gid_s, n)
            m = lanes.shape[1]
            state = jnp.concatenate(
                [jnp.full((n, 1), float(m)), s], axis=1)
            out.append([state, scnt])
        elif agg.fn == "array_agg":
            # scatter (group, within-group-rank) -> slot; NULL inputs
            # keep their position as sentinel slots (reference
            # ArrayAggregationFunction keeps nulls)
            at = state_types(agg)[0]
            cap_e = at.max_elems
            storage = at.np_dtype
            sent = _container_sent(storage)
            sel = rowsel
            gid_sel = jnp.where(sel, gid, n)
            rcnt = _gsum(ctx, sel.astype(jnp.int64), gid_sel, n)
            rank = _within_group_rank(gid_sel)
            vals = jnp.where(valid, data.astype(storage), sent)
            ok = sel & (rank < cap_e) & (gid_sel < n)
            tgt = jnp.where(ok, gid_sel.astype(jnp.int64) * cap_e + rank, n * cap_e)
            flat = jnp.full((n * cap_e,), sent, dtype=storage)
            flat = flat.at[tgt].set(vals, mode="drop")
            arr = flat.reshape(n, cap_e)
            length = jnp.minimum(rcnt, cap_e).astype(storage)
            out.append([jnp.concatenate([length[:, None], arr], axis=1), rcnt])
        elif agg.fn in ("map_agg", "hll_sketch"):
            # two scatters, same (group, rank) geometry: keys then
            # values (MapAggregationFunction analog); NULL-key rows drop
            mt = state_types(agg)[0]
            cap_e = mt.max_elems
            storage = mt.np_dtype
            sent = _container_sent(storage)
            v_data, v_valid = c.compile(agg.arg2)(page)
            sel = rowsel & valid  # keys must be non-null
            gid_sel = jnp.where(sel, gid, n)
            rcnt = _gsum(ctx, sel.astype(jnp.int64), gid_sel, n)
            rank = _within_group_rank(gid_sel)
            ok = sel & (rank < cap_e) & (gid_sel < n)
            tgt = jnp.where(ok, gid_sel.astype(jnp.int64) * cap_e + rank, n * cap_e)
            kflat = jnp.full((n * cap_e,), sent, dtype=storage)
            kflat = kflat.at[tgt].set(data.astype(storage), mode="drop")
            vflat = jnp.full((n * cap_e,), sent, dtype=storage)
            vflat = vflat.at[tgt].set(
                jnp.where(v_valid, v_data.astype(storage), sent), mode="drop")
            length = jnp.minimum(rcnt, cap_e).astype(storage)
            state = jnp.concatenate(
                [length[:, None], kflat.reshape(n, cap_e),
                 vflat.reshape(n, cap_e)], axis=1)
            out.append([state, rcnt])
        elif agg.fn == "multimap_agg":
            # map_agg geometry with ARRAY-valued lanes: the value half
            # is a (cap_e, 1+av) matrix per group, scattered row-wise
            mt = state_types(agg)[0]
            cap_e = mt.max_elems
            av = 1 + mt.element.max_elems
            storage = mt.np_dtype
            sent = _container_sent(storage)
            v_data, v_valid = c.compile(agg.arg2)(page)
            sel = rowsel & valid
            gid_sel = jnp.where(sel, gid, n)
            rcnt = _gsum(ctx, sel.astype(jnp.int64), gid_sel, n)
            rank = _within_group_rank(gid_sel)
            ok = sel & (rank < cap_e) & (gid_sel < n)
            tgt = jnp.where(ok, gid_sel.astype(jnp.int64) * cap_e + rank, n * cap_e)
            kflat = jnp.full((n * cap_e,), sent, dtype=storage)
            kflat = kflat.at[tgt].set(data.astype(storage), mode="drop")
            vrows = jnp.where(v_valid[:, None], v_data.astype(storage), sent)
            vflat = jnp.full((n * cap_e, av), sent, dtype=storage)
            vflat = vflat.at[tgt].set(vrows, mode="drop")
            length = jnp.minimum(rcnt, cap_e).astype(storage)
            state = jnp.concatenate(
                [length[:, None], kflat.reshape(n, cap_e),
                 vflat.reshape(n, cap_e * av)], axis=1)
            out.append([state, rcnt])
        elif agg.fn == "map_union":
            # union the entries of map-valued rows per group: flatten
            # each row's [len, keys.., vals..] into per-entry virtual
            # rows, then the map_agg (group, entry-rank) scatter
            # (MapUnionAggregation.java).  Deviation (engine-wide map
            # convention, see PARITY.md): duplicate keys keep every
            # occurrence — lookups take the first, but cardinality
            # counts entries, where the reference dedupes keys
            st = state_types(agg)[0]
            cap_e = st.max_elems
            storage = st.np_dtype
            sent = _container_sent(storage)
            cap_in = agg.arg.type.max_elems
            l0 = data[:, 0]
            if jnp.issubdtype(data.dtype, jnp.floating):
                l0 = jnp.where(jnp.isnan(l0), 0.0, l0)
            lens_in = jnp.maximum(l0.astype(jnp.int64), 0)
            sel = rowsel & valid
            j = jnp.arange(cap_in, dtype=jnp.int64)[None, :]
            entry_ok = sel[:, None] & (j < lens_in[:, None])
            egid = jnp.where(entry_ok, gid[:, None], n).reshape(-1)
            ecnt = _gsum(ctx, entry_ok.astype(jnp.int64).sum(axis=1),
                         gid_a, n)
            # the COUNT column tracks rows with non-null maps (empty
            # maps still make the group's result an empty map, not
            # NULL); the length lane tracks entries
            rows_cnt = _gsum(ctx, sel.astype(jnp.int64), gid_a, n)
            rank = _within_group_rank(egid)
            ok = entry_ok.reshape(-1) & (rank < cap_e) & (egid < n)
            tgt = jnp.where(ok, egid.astype(jnp.int64) * cap_e + rank,
                            n * cap_e)
            kflat = jnp.full((n * cap_e,), sent, dtype=storage)
            kflat = kflat.at[tgt].set(
                data[:, 1:1 + cap_in].reshape(-1).astype(storage),
                mode="drop")
            vflat = jnp.full((n * cap_e,), sent, dtype=storage)
            vflat = vflat.at[tgt].set(
                data[:, 1 + cap_in:1 + 2 * cap_in].reshape(-1).astype(storage),
                mode="drop")
            length = jnp.minimum(ecnt, cap_e).astype(storage)
            state = jnp.concatenate(
                [length[:, None], kflat.reshape(n, cap_e),
                 vflat.reshape(n, cap_e)], axis=1)
            out.append([state, rows_cnt])
        elif agg.fn == "make_set_digest":
            # KMV sketch build: hash the value, dedup per group summing
            # multiplicities, keep the K smallest hashes
            st = state_types(agg)[0]
            cap_e = st.max_elems
            storage = st.np_dtype
            sel = rowsel & valid
            if jnp.issubdtype(data.dtype, jnp.floating):
                v64 = jax.lax.bitcast_convert_type(
                    data.astype(jnp.float64), jnp.int64)
            else:
                v64 = data.astype(jnp.int64)
            h = mix64(v64)
            ones = jnp.ones_like(h)
            state, distinct = _kmv_lanes(gid, h, ones, sel, n, cap_e,
                                         storage)
            out.append([state, distinct])
        elif agg.fn == "merge_set_digest":
            # union of digest-valued rows: flatten their lanes and
            # re-lane (counts sum on shared hashes)
            st = state_types(agg)[0]
            cap_e = st.max_elems
            storage = st.np_dtype
            sel = rowsel & valid
            rows = jnp.where(sel[:, None], data.astype(storage),
                             jnp.zeros((), storage))
            egid, hs, cs, lane_ok = _digest_entries(
                rows, jnp.where(sel, gid, n), n, cap_e)
            state, distinct = _kmv_lanes(egid, hs, cs, lane_ok, n, cap_e,
                                         storage)
            out.append([state, distinct])
        elif agg.fn in ("max_n", "min_n", "max_by_n", "min_by_n"):
            # top-n per group via one value-ordered lexsort + scatter
            # (Max/MinNAggregationFunction's TypedHeap,
            # Max/MinByNAggregationFunction's TypedKeyValueHeap)
            st = state_types(agg)[0]
            cap_e = st.max_elems
            storage = st.np_dtype
            sent = _container_sent(storage)
            by = agg.fn in ("max_by_n", "min_by_n")
            if by:
                k_data, k_valid = c.compile(agg.arg2)(page)
                sel = rowsel & k_valid  # key must order; NULL x allowed
                keys = k_data
                vals = jnp.where(valid, data.astype(storage), sent)
            else:
                sel = rowsel & valid
                keys = data
                vals = data
            halves, gcnt = _topn_halves(
                ctx, gid, keys, vals, sel, n, cap_e, storage,
                descending=agg.fn in ("max_n", "max_by_n"), with_keys=by)
            length = jnp.minimum(gcnt, cap_e).astype(storage)
            state = jnp.concatenate([length[:, None]] + halves, axis=1)
            out.append([state, gcnt])
        else:
            raise KeyError(agg.fn)
    return out


#: aggregate fns whose packed-direct states combine POSITIONALLY —
#: slot i of one partial merges with slot i of another by pure
#: elementwise math (no sort, no scatter)
_POSITIONAL_FNS = frozenset({
    "count", "count_star", "sum", "sum0", "avg", "min", "max",
    "bitwise_and_agg", "bitwise_or_agg",
}) | set(VARIANCE_FNS)


def packed_direct_layout(group_exprs, key_domains, max_groups: int) -> bool:
    """THE packed-direct branch predicate (grouped_aggregate's own
    condition, exported so runners never hand-mirror it): exact scalar
    key domains whose product fits the direct-address budget.  Raw
    byte-matrix and multi-dim keys pack inexactly (pack_or_hash_keys
    returns exact=False for them), so they are excluded here too."""
    if not group_exprs or not key_domains             or any(d is None for d in key_domains):
        return False
    for e in group_exprs:
        t = getattr(e, "type", None)
        if t is None or t.is_raw_string or t.is_binary                 or t.value_shape != ():
            return False
    prod = 1
    for lo, hi in key_domains:
        prod *= hi - lo + 2
    return prod <= min(max_groups, DIRECT_GROUP_LIMIT)


def packed_fold_supported(aggs: Sequence[AggCall]) -> bool:
    """True when every aggregate's packed-direct state merges
    elementwise (raw-string min/max lane matrices excluded)."""
    for a in aggs:
        if a.fn not in _POSITIONAL_FNS:
            return False
        if a.fn in ("min", "max") and a.arg is not None \
                and (a.arg.type.is_raw_string
                     or a.arg.type.is_long_decimal):
            # lane matrices / limb vectors need lexicographic combines,
            # not per-component minimum
            return False
    return True


def _slice_state_cols(page: Page, num_keys: int, aggs):
    """(state columns, first-state dictionaries) per aggregate — ONE
    linear walk of the state layout (shared by the positional fold and
    finalize so the layout logic lives in one place)."""
    cols: List[List[jax.Array]] = []
    dicts: List[Optional[object]] = []
    pos = num_keys
    for agg in aggs:
        k = len(state_types(agg))
        cols.append([page.blocks[pos + j].data for j in range(k)])
        dicts.append(page.blocks[pos].dictionary)
        pos += k
    return cols, dicts


def combine_packed_states(a: Page, b: Page, num_keys: int,
                          aggs: Sequence[AggCall]) -> Page:
    """Fold two packed-direct partial pages ELEMENTWISE: group id ==
    packed key == slot position, so merging is vector adds/mins/maxes
    over aligned slots — the no-sort fast path the direct-address
    layout buys (dead slots hold the combine identities: 0 for sums,
    type extremes for min/max).  Variance states combine via Chan's
    pairwise formula, also elementwise."""
    ca, _ = _slice_state_cols(a, num_keys, aggs)
    cb, _ = _slice_state_cols(b, num_keys, aggs)
    out_blocks = list(a.blocks[:num_keys])
    pos = num_keys
    for agg, sa, sb in zip(aggs, ca, cb):
        sts = state_types(agg)
        if agg.fn in ("count", "count_star"):
            merged = [sa[0] + sb[0]]
        elif agg.fn in ("sum", "sum0", "avg") and agg.arg is not None \
                and sts[0].is_long_decimal:  # incl. widened short p>15 args
            from presto_tpu.ops import decimal128 as d128

            merged = [d128.add(sa[0], sb[0]), sa[1] + sb[1]]
        elif agg.fn in ("sum", "sum0", "avg"):
            merged = [sa[0] + sb[0], sa[1] + sb[1]]
        elif agg.fn == "min":
            merged = [jnp.minimum(sa[0], sb[0]), sa[1] + sb[1]]
        elif agg.fn == "max":
            merged = [jnp.maximum(sa[0], sb[0]), sa[1] + sb[1]]
        elif agg.fn == "bitwise_and_agg":
            merged = [sa[0] & sb[0], sa[1] + sb[1]]
        elif agg.fn == "bitwise_or_agg":
            merged = [sa[0] | sb[0], sa[1] + sb[1]]
        else:  # VARIANCE_FNS: (s, m2, cnt) via Chan's pairwise update
            s_a, m2a, n_a = sa
            s_b, m2b, n_b = sb
            naf = n_a.astype(jnp.float64)
            nbf = n_b.astype(jnp.float64)
            nf = jnp.maximum(naf + nbf, 1.0)
            mean_a = s_a / jnp.maximum(naf, 1.0)
            mean_b = s_b / jnp.maximum(nbf, 1.0)
            delta = mean_b - mean_a
            chan = m2a + m2b + delta * delta * naf * nbf / nf
            m2 = jnp.where(n_a == 0, m2b, jnp.where(n_b == 0, m2a, chan))
            merged = [s_a + s_b, m2, n_a + n_b]
        for st, col in zip(sts, merged):
            blk = a.blocks[pos]
            out_blocks.append(Block(col.astype(st.np_dtype),
                                    a.blocks[pos].valid | b.blocks[pos].valid,
                                    st, blk.dictionary))
            pos += 1
    mask = a.row_mask | b.row_mask
    return Page(tuple(out_blocks), mask)


def finalize_packed(acc: Page, num_keys: int,
                    aggs: Sequence[AggCall]) -> Page:
    """mode='single' finalize of a packed-direct accumulator WITHOUT
    re-grouping: slots already hold one group each."""
    states, agg_dicts = _slice_state_cols(acc, num_keys, aggs)
    agg_blocks = _finalize(states, aggs, agg_dicts)
    mask = acc.row_mask
    agg_blocks = [Block(b.data, b.valid & mask, b.type, b.dictionary)
                  for b in agg_blocks]
    return Page(tuple(acc.blocks[:num_keys]) + tuple(agg_blocks), mask)


def mix64(v: jax.Array) -> jax.Array:
    """splitmix64 (golden-ratio increment + the _mix64 finalizer below):
    int64 value -> well-mixed int64 hash — the hash behind
    make_set_digest's KMV slots (the reference's XxHash64 role for
    SetDigest.add)."""
    z = v.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    return _mix64(z).astype(jnp.int64)


def _kmv_lanes(egid, hashes, counts, sel, n, cap_e, storage):
    """Per-group KMV digest state from entry rows: dedup (group, hash)
    runs summing their counts, keep each group's cap_e SMALLEST hashes
    in ascending lanes.  Returns (state [len, hashes.., counts..],
    distinct_total) — the sketch construction AND the sketch union are
    this one kernel (SetDigest.mergeWith collapses to re-laning)."""
    sent = _container_sent(storage)
    m = hashes.shape[0]
    egid = jnp.where(sel, egid, n)
    order = jnp.lexsort((hashes, egid))
    gs, hs, cs, sl = egid[order], hashes[order], counts[order], sel[order]
    newrun = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), (gs[1:] != gs[:-1]) | (hs[1:] != hs[:-1])])
    first = sl & newrun
    rid = jnp.cumsum(first.astype(jnp.int64)) - 1
    rsum = jnp.zeros((m + 1,), jnp.int64).at[
        jnp.where(sl, rid, m)].add(cs.astype(jnp.int64))
    # distinct rank within group (ascending hash): run id offset by the
    # group's first run id (rid is nondecreasing over sorted rows)
    gfirst = jnp.concatenate([jnp.ones(1, jnp.bool_), gs[1:] != gs[:-1]])
    gstart = jax.lax.cummax(jnp.where(gfirst & sl, rid, 0))
    rank_d = rid - gstart
    ok = first & (rank_d < cap_e) & (gs < n)
    tgt = jnp.where(ok, gs.astype(jnp.int64) * cap_e + rank_d, n * cap_e)
    hflat = jnp.full((n * cap_e,), sent, dtype=storage)
    hflat = hflat.at[tgt].set(hs.astype(storage), mode="drop")
    cflat = jnp.full((n * cap_e,), sent, dtype=storage)
    cflat = cflat.at[tgt].set(
        rsum[jnp.clip(rid, 0, m)].astype(storage), mode="drop")
    distinct = jnp.zeros((n + 1,), jnp.int64).at[
        jnp.where(first, gs, n)].add(1)[:n]
    length = jnp.minimum(distinct, cap_e).astype(storage)
    state = jnp.concatenate(
        [length[:, None], hflat.reshape(n, cap_e), cflat.reshape(n, cap_e)],
        axis=1)
    return state, distinct


def _digest_entries(arr_col, gid, n, cap_e):
    """Flatten digest-state rows into per-entry (egid, hash, count,
    sel) vectors for re-laning."""
    l0 = arr_col[:, 0]
    lens = jnp.where(gid < n, jnp.maximum(l0.astype(jnp.int64), 0), 0)
    j = jnp.arange(cap_e, dtype=jnp.int64)[None, :]
    lane_ok = j < jnp.minimum(lens, cap_e)[:, None]
    hashes = arr_col[:, 1:1 + cap_e]
    counts = arr_col[:, 1 + cap_e:1 + 2 * cap_e]
    egid = jnp.where(lane_ok, gid[:, None], n)
    return (egid.reshape(-1), hashes.reshape(-1), counts.reshape(-1),
            lane_ok.reshape(-1))


def _container_sent(storage):
    if jnp.issubdtype(storage, jnp.floating):
        return jnp.asarray(jnp.nan, dtype=storage)
    return jnp.asarray(jnp.iinfo(storage).min, dtype=storage)


def _ordered_rank(gid: jax.Array, order: jax.Array) -> jax.Array:
    """0-based position of each element within its gid class when the
    elements are visited in ``order`` (a permutation that clusters equal
    gids together)."""
    gs = gid[order]
    idx = jnp.arange(gs.shape[0], dtype=jnp.int64)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), gs[1:] != gs[:-1]])
    start = jax.lax.cummax(jnp.where(first, idx, 0))
    rank_sorted = idx - start
    return jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)


def _within_group_rank(gid: jax.Array) -> jax.Array:
    """0-based occurrence index of each row within its gid class
    (stable: earlier rows get lower ranks)."""
    return _ordered_rank(gid, jnp.argsort(gid, stable=True))


def _topn_halves(ctx, egid, keys, vals, sel, n, cap_e, storage,
                 descending, with_keys):
    """Scatter each group's cap_e extreme elements (ordered by ``keys``)
    into dense (n, cap_e) lanes, vals sorted by key — descending for
    max-forms, ascending for min-forms.

    The descending lane index is (group size - 1 - ascending rank), so
    no key negation is needed (int64 min would overflow under negation).
    Returns ([vals_lanes] or [vals_lanes, keys_lanes], live_count).
    TypedHeap.java analog: the heap becomes one lexsort + one scatter.
    """
    sent = _container_sent(storage)
    egid = jnp.where(sel, egid, n)
    gcnt = _gsum(ctx, sel.astype(jnp.int64), egid, n)
    order = jnp.lexsort((keys, egid))
    rank = _ordered_rank(egid, order)
    if descending:
        size_e = jnp.where(sel, gcnt[jnp.clip(egid, 0, n - 1)], 0)
        lane = size_e - 1 - rank
    else:
        lane = rank
    ok = sel & (lane >= 0) & (lane < cap_e) & (egid < n)
    tgt = jnp.where(ok, egid.astype(jnp.int64) * cap_e + lane, n * cap_e)
    vflat = jnp.full((n * cap_e,), sent, dtype=storage)
    vflat = vflat.at[tgt].set(vals.astype(storage), mode="drop")
    halves = [vflat.reshape(n, cap_e)]
    if with_keys:
        kflat = jnp.full((n * cap_e,), sent, dtype=storage)
        kflat = kflat.at[tgt].set(keys.astype(storage), mode="drop")
        halves.append(kflat.reshape(n, cap_e))
    return halves, gcnt


@jax.named_scope("agg:reduce")
def _merge_states(state_cols: List[List[jax.Array]], aggs, gid, n,
                  ctx: "Optional[_SortCtx]" = None):
    """Merge partial-state rows (one row per upstream group) into final
    groups: sums/counts add, mins/maxes reduce."""
    out: List[List[jax.Array]] = []
    for agg, cols in zip(aggs, state_cols):
        if agg.fn in ("count", "count_star"):
            out.append([_gsum(ctx, cols[0], gid, n)])
        elif agg.fn in ("sum", "sum0", "avg") and agg.arg is not None \
                and state_types(agg)[0].is_long_decimal:
            from presto_tpu.ops import decimal128 as d128

            live_rows = cols[1] > 0
            limbs = jnp.where(live_rows[:, None], d128.to_sum_limbs(cols[0]), 0)
            out.append([
                d128.from_sum_limbs(_gsum(ctx, limbs, gid, n)),
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn in ("sum", "sum0", "avg"):
            out.append([
                _gsum(ctx, cols[0], gid, n),
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn in ("min", "max") and agg.arg is not None \
                and agg.arg.type.is_long_decimal:
            nonnull = cols[1] > 0
            gid_nn = jnp.where(nonnull, gid, n)
            out.append(
                _minmax_long(agg.fn, cols[0], nonnull, gid_nn, n)
                + [_gsum(ctx, cols[1], gid, n)]
            )
        elif agg.fn in ("min", "max") and agg.arg is not None \
                and agg.arg.type.is_raw_string:
            from presto_tpu.ops import rawstring as rs

            nonnull = cols[1] > 0
            gid_nn = jnp.where(nonnull, gid, n)
            lanes = rs.pack_lanes(cols[0])
            best = _minmax_lanes(agg.fn, lanes, nonnull, gid_nn, n)
            out.append([
                rs.unpack_lanes(best, cols[0].shape[-1]),
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn == "min":
            out.append([
                _seg_min(cols[0], gid, n + 1)[:n],
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn == "max":
            out.append([
                _seg_max(cols[0], gid, n + 1)[:n],
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn in VARIANCE_FNS:
            # Chan's pairwise combination generalized to k partials:
            # M2 = Σ M2ᵢ + Σ cᵢ·(μᵢ − μ)²  with μ the combined mean.
            s_i, m2_i, c_i = cols
            s = _gsum(ctx, s_i, gid, n)
            cnt = _gsum(ctx, c_i, gid, n)
            mu = s / jnp.maximum(cnt, 1).astype(jnp.float64)
            cf_i = c_i.astype(jnp.float64)
            mu_i = s_i / jnp.maximum(cf_i, 1.0)
            dev = jnp.where(c_i > 0, mu_i - mu[jnp.clip(gid, 0, n - 1)], 0.0)
            m2 = _gsum(ctx, m2_i + cf_i * dev * dev, gid, n)
            out.append([s, m2, cnt])
        elif agg.fn in MOMENT_FNS:
            # Chan's pairwise combination generalized to M3/M4 with
            # δi = μi − μ (Σ ci δi = 0):
            #   M3 += 3 M2i δi + ci δi³
            #   M4 += 4 M3i δi + 6 M2i δi² + ci δi⁴
            s_i, m2_i, m3_i, m4_i, c_i = cols
            s = _gsum(ctx, s_i, gid, n)
            cnt = _gsum(ctx, c_i, gid, n)
            mu = s / jnp.maximum(cnt, 1).astype(jnp.float64)
            cf = c_i.astype(jnp.float64)
            mu_i = s_i / jnp.maximum(cf, 1.0)
            d = jnp.where(c_i > 0, mu_i - mu[jnp.clip(gid, 0, n - 1)], 0.0)
            d2 = d * d
            m2 = _gsum(ctx, m2_i + cf * d2, gid, n)
            m3 = _gsum(ctx, m3_i + 3.0 * m2_i * d + cf * d2 * d, gid, n)
            m4 = _gsum(ctx, m4_i + 4.0 * m3_i * d + 6.0 * m2_i * d2
                       + cf * d2 * d2, gid, n)
            out.append([s, m2, m3, m4, cnt])
        elif agg.fn in BITWISE_FNS:
            is_and = agg.fn == "bitwise_and_agg"
            ident = jnp.int64(-1) if is_and else jnp.int64(0)
            op = jnp.bitwise_and if is_and else jnp.bitwise_or
            has = cols[1] > 0
            v = jnp.where(has, cols[0], ident)
            acc = _seg_assoc(op, ident, v, jnp.where(gid < n, gid, n), n)
            out.append([acc, _gsum(ctx, cols[1], gid, n)])
        elif agg.fn in ("bool_and", "bool_or", "every"):
            out.append([_gsum(ctx, c, gid, n) for c in cols])
        elif agg.fn in COVAR_FNS:
            zero = [jnp.where(gid < n, c, jnp.zeros_like(c)) for c in cols]
            out.append([_gsum(ctx, c, gid, n) for c in zero])
        elif agg.fn == "checksum":
            out.append([_gsum(ctx, jnp.where(gid < n, cols[0], 0), gid, n)])
        elif agg.fn in ("min_by", "max_by"):
            x_i, xv_i, y_i, c_i = cols
            sel = c_i > 0
            gid_y = jnp.where(sel, gid, n)
            ycnt = _gsum(ctx, c_i, gid_y, n)
            if agg.fn == "min_by":
                yfill = _type_max(agg.arg2.type)
                y_best = _seg_min(
                    jnp.where(sel, y_i, yfill), gid_y, n + 1)[:n]
            else:
                yfill = _type_min(agg.arg2.type)
                y_best = _seg_max(
                    jnp.where(sel, y_i, yfill), gid_y, n + 1)[:n]
            tie = sel & (y_i == y_best[jnp.clip(gid_y, 0, n - 1)])
            xv_in = tie & (xv_i > 0)
            x_best = _seg_max(
                jnp.where(xv_in, x_i, _type_min(agg.arg.type)),
                jnp.where(xv_in, gid, n), n + 1)[:n]
            xv_cnt = _gsum(ctx, xv_in.astype(jnp.int64), jnp.where(xv_in, gid, n), n)
            out.append([x_best, (xv_cnt > 0).astype(jnp.int64), y_best, ycnt])
        elif agg.fn == "hll_merge":
            out.append([
                _gsum(ctx, cols[0], gid, n),
                _gsum(ctx, cols[1], gid, n),
            ])
        elif agg.fn in ("learn_regressor", "learn_classifier",
                        "evaluate_classifier_predictions"):
            arr, cnt = cols
            zero_dead = jnp.where((gid < n)[:, None], arr,
                                  jnp.zeros((), arr.dtype))
            out.append([
                _gsum(ctx, zero_dead, gid, n),
                _gsum(ctx, cnt, gid, n),
            ])
        elif agg.fn in ("array_agg", "map_agg", "hll_sketch",
                        "multimap_agg", "map_union"):
            # concatenate partial containers per group: each partial
            # row's elements land at the group's running offset (stable
            # order).  Halves: arrays have one value lane per rank; maps
            # add a key half; multimaps' value half is an (av)-wide
            # matrix per rank — all three share the offset geometry.
            arr_col, cnt_col = cols
            at = state_types(agg)[0]
            cap_e = at.max_elems
            storage = arr_col.dtype
            sent = _container_sent(storage)
            l0 = arr_col[:, 0]
            if jnp.issubdtype(storage, jnp.floating):
                l0 = jnp.where(jnp.isnan(l0), 0.0, l0)
            lens = jnp.where(gid < n, jnp.maximum(l0.astype(jnp.int64), 0), 0)
            order = jnp.argsort(gid, stable=True)
            gs = gid[order]
            lens_s = lens[order]
            cum = jnp.cumsum(lens_s) - lens_s  # global exclusive prefix
            first = jnp.concatenate([jnp.ones(1, jnp.bool_), gs[1:] != gs[:-1]])
            base = jax.lax.cummax(jnp.where(first, cum, 0))
            off_s = cum - base
            off = jnp.zeros_like(off_s).at[order].set(off_s)
            j = jnp.arange(cap_e, dtype=jnp.int64)[None, :]
            ok = (j < lens[:, None]) & ((off[:, None] + j) < cap_e) & (gid < n)[:, None]
            tgt = jnp.where(
                ok, gid.astype(jnp.int64)[:, None] * cap_e + off[:, None] + j,
                n * cap_e,
            )
            total = _gsum(ctx, lens, gid, n)
            length = jnp.minimum(total, cap_e).astype(storage)
            if agg.fn == "array_agg":
                widths = [1]
            elif agg.fn == "multimap_agg":
                widths = [1, 1 + at.element.max_elems]
            else:
                widths = [1, 1]
            halves = []
            o = 1
            for w in widths:
                seg = arr_col[:, o: o + cap_e * w].reshape(-1, w)
                flat = jnp.full((n * cap_e, w), sent, dtype=storage)
                flat = flat.at[tgt.reshape(-1)].set(seg, mode="drop")
                halves.append(flat.reshape(n, cap_e * w))
                o += cap_e * w
            out.append([
                jnp.concatenate([length[:, None]] + halves, axis=1),
                _gsum(ctx, cnt_col, gid, n),
            ])
        elif agg.fn in ("make_set_digest", "merge_set_digest"):
            # KMV union: the K smallest of the union of per-partial
            # K-smallest lanes IS the union's K smallest (semilattice),
            # with counts summing on shared hashes
            arr_col, cnt_col = cols
            cap_e = state_types(agg)[0].max_elems
            storage = arr_col.dtype
            egid, hs, cs, lane_ok = _digest_entries(arr_col, gid, n, cap_e)
            state, _ = _kmv_lanes(egid, hs, cs, lane_ok, n, cap_e, storage)
            # distinct totals OVERCOUNT across partials (shared hashes);
            # the estimator only reads them below cap_e, where the lane
            # union is exact — recompute from the merged lanes
            merged_distinct = state[:, 0].astype(jnp.int64)
            total = _gsum(ctx, cnt_col, gid, n)
            distinct = jnp.where(merged_distinct < cap_e, merged_distinct,
                                 jnp.maximum(total, merged_distinct))
            state = state.at[:, 0].set(
                jnp.minimum(distinct, cap_e).astype(storage))
            out.append([state, distinct])
        elif agg.fn in ("max_n", "min_n", "max_by_n", "min_by_n"):
            # top-n of the union of per-partial top-n lanes IS the
            # global top-n (semilattice), so merging re-runs the same
            # ordered scatter over the flattened lanes
            arr_col, cnt_col = cols
            cap_e = state_types(agg)[0].max_elems
            storage = arr_col.dtype
            by = agg.fn in ("max_by_n", "min_by_n")
            l0 = arr_col[:, 0]
            if jnp.issubdtype(storage, jnp.floating):
                l0 = jnp.where(jnp.isnan(l0), 0.0, l0)
            lens = jnp.where(gid < n, jnp.maximum(l0.astype(jnp.int64), 0), 0)
            j = jnp.arange(cap_e, dtype=jnp.int64)[None, :]
            lane_ok = j < jnp.minimum(lens, cap_e)[:, None]
            vals = arr_col[:, 1:1 + cap_e]
            keys = arr_col[:, 1 + cap_e:1 + 2 * cap_e] if by else vals
            egid = jnp.where(lane_ok, gid[:, None], n)
            # ctx=None: the sort ctx's runs cover row-length arrays,
            # not the rows*cap_e flattened lanes
            halves, _ = _topn_halves(
                None, egid.reshape(-1), keys.reshape(-1), vals.reshape(-1),
                lane_ok.reshape(-1), n, cap_e, storage,
                descending=agg.fn in ("max_n", "max_by_n"), with_keys=by)
            total = _gsum(ctx, cnt_col, gid, n)
            length = jnp.minimum(total, cap_e).astype(storage)
            out.append([
                jnp.concatenate([length[:, None]] + halves, axis=1), total,
            ])
        else:
            raise KeyError(agg.fn)
    return out


def _agg_dict(agg: AggCall, dictionaries) -> Optional[object]:
    """Dictionary carried through value-preserving aggregates
    (min/max/min_by/max_by of a VARCHAR argument)."""
    if agg.fn not in ("min", "max", "min_by", "max_by", "array_agg"):
        return None
    if agg.arg is None or not agg.arg.type.is_string:
        return None
    from presto_tpu.expr.compile import expr_dictionary

    return expr_dictionary(agg.arg, dictionaries)


# (id(dict)) -> (dict ref, rank list, inv list); host lists so nothing
# device-resident leaks across traces (cached: the sort is O(n log n)
# per dictionary and the eager spill path calls kernels per page)
_COLLATION_CACHE: dict = {}

# (id(dict)) -> (dict ref, has_duplicate_values) — derived dictionaries
# (substr, date_format, day_name...) may map MANY codes to one value
_DUP_CACHE: dict = {}


def _dict_has_duplicates(d) -> bool:
    got = _DUP_CACHE.get(id(d))
    if got is not None:
        return got[1]
    dup = len(set(d.values)) < len(d.values)
    _DUP_CACHE[id(d)] = (d, dup)
    return dup


def canonicalize_codes(datas, dicts):
    """Replace each dictionary-coded key column's codes with the
    representative code of their VALUE class when the dictionary holds
    duplicate values — grouping, DISTINCT, joins, window partitions and
    exchange routing must follow value equality, not code identity.
    Non-string columns and injective dictionaries pass through
    untouched (the common case: zero cost)."""
    out = []
    for d, dic in zip(datas, dicts):
        if dic is None or not _dict_has_duplicates(dic):
            out.append(d)
            continue
        rank, inv = _collation_luts(dic)
        c = jnp.clip(d, 0, rank.shape[0] - 1)
        out.append(inv[rank[c]].astype(d.dtype))
    return out


def expr_key_dicts(page: Page, exprs) -> list:
    """Dictionary provenance per key expression (None for non-string)."""
    from presto_tpu.expr.compile import expr_dictionary

    dicts = [b.dictionary for b in page.blocks]
    return [expr_dictionary(e, dicts) if e.type.is_string else None
            for e in exprs]


def _collation_luts(d) -> Tuple[jax.Array, jax.Array]:
    """(code -> collation rank, rank -> representative code) LUTs.
    Dictionary codes are assignment-ordered, not collation-ordered, so
    string min/max must reduce over ranks (duplicate values share a
    rank; the inverse picks a representative code)."""
    cached = _COLLATION_CACHE.get(id(d))
    if cached is not None:
        _, rank, inv = cached
        return (jnp.asarray(rank, dtype=jnp.int32), jnp.asarray(inv, dtype=jnp.int32))
    values = d.values
    order = sorted(range(len(values)), key=lambda i: values[i])
    rank = [0] * len(values)
    inv = [0] * len(values)
    prev = None
    r = 0
    for pos, i in enumerate(order):
        if values[i] != prev:
            r = pos
            prev = values[i]
            inv[r] = i
        rank[i] = r
    _COLLATION_CACHE[id(d)] = (d, rank, inv)
    return (jnp.asarray(rank, dtype=jnp.int32), jnp.asarray(inv, dtype=jnp.int32))


def _finalize(states: List[List[jax.Array]], aggs, agg_dicts=None) -> List[Block]:
    blocks = []
    agg_dicts = agg_dicts or [None] * len(aggs)
    for agg, cols, adict in zip(aggs, states, agg_dicts):
        t = output_type(agg)
        if agg.fn in ("count", "count_star"):
            blocks.append(Block(cols[0].astype(jnp.int64), jnp.ones_like(cols[0], jnp.bool_), t))
        elif agg.fn in ("sum", "sum0"):
            # sum0 = sum with 0-on-empty: the outer half of a decomposed
            # plain count in the mixed-DISTINCT rewrite (never NULL)
            s, cnt = cols
            st = _sum_type(agg.arg.type) if agg.arg is not None else t
            if st.is_long_decimal and agg.type.is_decimal \
                    and not agg.type.is_long_decimal:
                # outer half of a decomposed sum (mixed-DISTINCT
                # rewrite): the fold runs in widened limbs because the
                # partial-sum argument types as p=18, but the plan keeps
                # the original short output type — collapse like avg
                s = s[..., 0] * jnp.int64(10 ** 18) + s[..., 1]
                t = agg.type
            valid = cnt > 0 if agg.fn == "sum" \
                else jnp.ones_like(cnt, jnp.bool_)
            blocks.append(Block(s.astype(t.np_dtype), valid, t))
        elif agg.fn == "avg":
            s, cnt = cols
            st = _sum_type(agg.arg.type)
            n = jnp.maximum(cnt, 1)
            if t.is_decimal and st.is_long_decimal:
                # exact unscaled-sum / count, HALF_UP, staying decimal
                q = _avg_decimal128(s, n)
                if not t.is_long_decimal:
                    # widened accumulator over a short p>15 argument:
                    # the per-group mean fits the argument type again
                    q = q[..., 0] * jnp.int64(10 ** 18) + q[..., 1]
                blocks.append(Block(q, cnt > 0, t))
            elif t.is_decimal:
                av = jnp.abs(s)
                sign = jnp.where(s < 0, -1, 1)
                # overflow-free HALF_UP away from zero (2*av could wrap
                # for sums near the decimal(18) accumulator ceiling)
                q = av // n
                q = q + (2 * (av - q * n) >= n).astype(q.dtype)
                blocks.append(Block((sign * q).astype(t.np_dtype), cnt > 0, t))
            elif t.name.startswith("interval"):
                # exact integer average with HALF-UP away from zero —
                # same machinery as the decimal branch (float division
                # would lose microseconds once the sum passes 2^53)
                av = jnp.abs(s)
                sign = jnp.where(s < 0, -1, 1)
                q = av // n
                q = q + (2 * (av - q * n) >= n).astype(q.dtype)
                blocks.append(Block((sign * q).astype(jnp.int64),
                                    cnt > 0, t))
            else:
                num = s.astype(jnp.float64)
                d = num / n.astype(jnp.float64)
                blocks.append(Block(d, cnt > 0, t))
        elif agg.fn in ("min", "max"):
            m, cnt = cols
            if adict is not None:
                # state holds collation ranks; map back to codes
                _, inv_lut = _collation_luts(adict)
                m = inv_lut[jnp.clip(m.astype(jnp.int32), 0, inv_lut.shape[0] - 1)]
            blocks.append(Block(m.astype(t.np_dtype), cnt > 0, t, adict))
        elif agg.fn in VARIANCE_FNS:
            s, m2, cnt = cols
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            pop_var = jnp.maximum(m2 / n, 0.0)
            sample = agg.fn in ("stddev", "stddev_samp", "variance", "var_samp")
            if sample:
                var = pop_var * n / jnp.maximum(n - 1, 1)
                valid = cnt > 1
            else:
                var = pop_var
                valid = cnt > 0
            out_v = jnp.sqrt(var) if agg.fn.startswith("stddev") else var
            blocks.append(Block(out_v, valid, t))
        elif agg.fn in COVAR_FNS:
            sx, sy, sxy, sxx, syy, cnt = cols
            nf = jnp.maximum(cnt, 1).astype(jnp.float64)
            cov = sxy / nf - (sx / nf) * (sy / nf)
            varx = jnp.maximum(sxx / nf - (sx / nf) ** 2, 0.0)
            vary = jnp.maximum(syy / nf - (sy / nf) ** 2, 0.0)
            if agg.fn == "covar_pop":
                v, ok = cov, cnt > 0
            elif agg.fn == "covar_samp":
                v = cov * nf / jnp.maximum(nf - 1, 1.0)
                ok = cnt > 1
            elif agg.fn == "corr":
                denom = jnp.sqrt(varx * vary)
                v = cov / jnp.where(denom == 0, 1.0, denom)
                ok = (cnt > 1) & (denom > 0)
            elif agg.fn == "regr_slope":
                v = cov / jnp.where(varx == 0, 1.0, varx)
                ok = (cnt > 1) & (varx > 0)
            else:  # regr_intercept
                slope = cov / jnp.where(varx == 0, 1.0, varx)
                v = sy / nf - slope * (sx / nf)
                ok = (cnt > 1) & (varx > 0)
            blocks.append(Block(v, ok, t))
        elif agg.fn == "checksum":
            blocks.append(Block(cols[0].astype(jnp.int64),
                                jnp.ones_like(cols[0], jnp.bool_), t))
        elif agg.fn in ("bool_and", "bool_or", "every"):
            trues, cnt = cols
            if agg.fn == "bool_or":
                v = trues > 0
            else:
                v = trues == cnt
            blocks.append(Block(v, cnt > 0, t))
        elif agg.fn in MOMENT_FNS:
            _s, m2, m3, m4, cnt = cols
            nf = jnp.maximum(cnt, 1).astype(jnp.float64)
            safe_m2 = jnp.where(m2 == 0, 1.0, m2)
            if agg.fn == "skewness":
                # sqrt(n) * M3 / M2^1.5 (CentralMomentsAggregation)
                v = jnp.sqrt(nf) * m3 / jnp.power(safe_m2, 1.5)
                ok = (cnt >= 3) & (m2 > 0)
            else:
                # unbiased sample excess kurtosis (Σd⁴/s⁴ with
                # s² = M2/(n−1)):
                #   n(n+1)(n−1)/((n−2)(n−3)) · M4/M2² −
                #   3(n−1)²/((n−2)(n−3))
                d1, d2, d3 = nf - 1.0, jnp.maximum(nf - 2.0, 1.0), \
                    jnp.maximum(nf - 3.0, 1.0)
                v = (nf * (nf + 1.0) * d1 / (d2 * d3)
                     * (m4 / (safe_m2 * safe_m2))
                     - 3.0 * d1 * d1 / (d2 * d3))
                ok = (cnt >= 4) & (m2 > 0)
            blocks.append(Block(v, ok, t))
        elif agg.fn in BITWISE_FNS:
            acc, cnt = cols
            blocks.append(Block(acc.astype(jnp.int64), cnt > 0, t))
        elif agg.fn in ("min_by", "max_by"):
            x, xv, _y, cnt = cols
            blocks.append(Block(x.astype(t.np_dtype), (cnt > 0) & (xv > 0), t, adict))
        elif agg.fn == "learn_regressor":
            s, cnt = cols
            dim = agg.arg2.type.max_elems + 1
            n = s.shape[0]
            xtx = s[:, 1 : 1 + dim * dim].reshape(n, dim, dim)
            xty = s[:, 1 + dim * dim : 1 + dim * dim + dim]
            # tiny ridge keeps rank-deficient groups solvable
            reg = 1e-8 * jnp.eye(dim)[None, :, :]
            w = jnp.linalg.solve(xtx + reg, xty[..., None])[..., 0]
            model = jnp.concatenate([jnp.full((n, 1), float(dim)), w], axis=1)
            blocks.append(Block(model.astype(t.np_dtype), cnt > 0, t))
        elif agg.fn == "learn_classifier":
            s, cnt = cols
            k = agg.arg2.type.max_elems
            C = ML_MAX_CLASSES
            n = s.shape[0]
            counts = s[:, 1 : 1 + C]
            sumx = s[:, 1 + C : 1 + C + C * k].reshape(n, C, k)
            sumx2 = s[:, 1 + C + C * k : 1 + C + 2 * C * k].reshape(n, C, k)
            total = jnp.maximum(jnp.sum(counts, axis=1, keepdims=True), 1.0)
            prior = counts / total
            cc = jnp.maximum(counts, 1.0)[:, :, None]
            mean = sumx / cc
            var = jnp.maximum(sumx2 / cc - mean ** 2, 1e-9)
            model = jnp.concatenate([
                jnp.full((n, 1), float(1 + C * (1 + 2 * k))),
                jnp.full((n, 1), float(C)),
                prior, mean.reshape(n, C * k), var.reshape(n, C * k),
            ], axis=1)
            blocks.append(Block(model.astype(t.np_dtype), cnt > 0, t))
        elif agg.fn in ("array_agg", "map_agg", "hll_sketch",
                        "multimap_agg", "map_union", "max_n", "min_n",
                        "make_set_digest", "merge_set_digest"):
            arr_state, cnt = cols
            blocks.append(Block(arr_state.astype(t.np_dtype), cnt > 0, t, adict))
        elif agg.fn == "evaluate_classifier_predictions":
            # transient: int64 count matrix under the VARCHAR type —
            # LocalRunner._host_finalize_aggs rewrites it to dictionary
            # codes immediately after the final merge (strings cannot
            # be built inside jit)
            arr_state, cnt = cols
            blocks.append(Block(arr_state.astype(jnp.int64), cnt > 0, t))
        elif agg.fn in ("max_by_n", "min_by_n"):
            # drop the ordering-key half of the state; convert the
            # shared-storage sentinel to the output array's own
            cap_e = state_types(agg)[0].max_elems
            arr_state, cnt = cols
            sub = arr_state[:, :1 + cap_e]
            if jnp.issubdtype(sub.dtype, jnp.floating) \
                    and not jnp.issubdtype(t.np_dtype, jnp.floating):
                osent = _container_sent(t.np_dtype)
                body = jnp.where(jnp.isnan(sub[:, 1:]),
                                 jnp.float64(osent), sub[:, 1:])
                l0 = jnp.where(jnp.isnan(sub[:, :1]), 0.0, sub[:, :1])
                sub = jnp.concatenate([l0, body], axis=1)
            blocks.append(Block(sub.astype(t.np_dtype), cnt > 0, t, adict))
        elif agg.fn == "hll_merge":
            # HLL estimator with linear-counting small-range correction
            # (airlift HyperLogLog / the original Flajolet et al. paper)
            s, present = cols
            m = float(HLL_M)
            alpha = 0.7213 / (1.0 + 1.079 / m)
            zeros = m - present.astype(jnp.float64)
            s_full = s + zeros  # absent buckets contribute 2^-0 = 1
            raw = alpha * m * m / jnp.maximum(s_full, 1e-12)
            lc = m * jnp.log(m / jnp.maximum(zeros, 1.0))
            est = jnp.where((raw <= 2.5 * m) & (zeros > 0), lc, raw)
            blocks.append(Block(jnp.round(est).astype(jnp.int64),
                                jnp.ones_like(present, jnp.bool_), t))
        else:
            raise KeyError(agg.fn)
    return blocks


def _avg_decimal128(s: jax.Array, n: jax.Array) -> jax.Array:
    """Exact limb-decimal sum divided by int64 count with HALF_UP
    rounding, keeping the unscaled representation — the finalize of
    avg(decimal) over a limb accumulator.  Long division over base-10^6
    (or, for wide 5-limb sums, base-10^9) digits so the running
    remainder times the base never overflows int64 (sound for counts <
    2^43 / 2^33 respectively — far above any page capacity)."""
    from presto_tpu.ops import decimal128 as d128

    neg = s[..., 0] < 0
    a = jnp.where(neg[..., None], d128.neg(s), s)
    if a.shape[-1] == d128.WIDE_LIMBS:
        r = jnp.zeros_like(n)
        qs = []
        for i in range(d128.WIDE_LIMBS):
            cur = r * jnp.int64(d128._B9) + a[..., i]
            qs.append(jnp.floor_divide(cur, n))
            r = cur - qs[-1] * n
        q = jnp.stack(qs, axis=-1)
        q = q.at[..., -1].add((2 * r >= n).astype(jnp.int64))
        q = d128._norm_wide(q)
        return jnp.where(neg[..., None], d128.neg(q), q)
    hi, lo = a[..., 0], a[..., 1]
    m = jnp.int64(1_000_000)
    digits = [hi // (m * m), (hi // m) % m, hi % m,
              lo // (m * m), (lo // m) % m, lo % m]
    r = jnp.zeros_like(n)
    qs = []
    for d in digits:
        cur = r * m + d
        qs.append(cur // n)
        r = cur % n
    q_hi = (qs[0] * m + qs[1]) * m + qs[2]
    q_lo = (qs[3] * m + qs[4]) * m + qs[5]
    q_lo = q_lo + (2 * r >= n).astype(jnp.int64)  # HALF_UP
    q = d128.normalize(q_hi, q_lo)
    return jnp.where(neg[..., None], d128.neg(q), q)


def _type_max(t: Type):
    return jnp.asarray(jnp.finfo(jnp.float64).max if t.name == "double" else _I64_MAX).astype(t.np_dtype)


def _type_min(t: Type):
    return jnp.asarray(jnp.finfo(jnp.float64).min if t.name == "double" else -_I64_MAX - 1).astype(t.np_dtype)


def _minmax_lanes(fn: str, lanes, nonnull, gid_nn, n):
    """k-phase lexicographic segment extreme over (rows, k) int64
    lanes: phase c reduces lane c among rows still tying on lanes
    < c (generalizes _minmax_long's two-limb walk)."""
    red = _seg_min if fn == "min" else _seg_max
    fill = _I64_MAX if fn == "min" else -_I64_MAX - 1
    tie = nonnull
    gid_cur = gid_nn
    best = []
    for c in range(lanes.shape[-1]):
        b = red(jnp.where(tie, lanes[..., c], fill), gid_cur, n + 1)[:n]
        best.append(b)
        tie = tie & (lanes[..., c] == b[jnp.clip(gid_cur, 0, n - 1)])
        gid_cur = jnp.where(tie, gid_nn, n)
    return jnp.stack(best, axis=-1)


def _minmax_long(fn: str, data, nonnull, gid_nn, n):
    """Phased lexicographic extreme over limb vectors, msb limb first —
    canonical limb order IS value order (limbs[1:] in [0, base)).
    Works for both the (.., 2) and the wide (.., 5) layouts."""
    L = int(data.shape[-1])
    if fn == "min":
        red, fill = _seg_min, _I64_MAX
    else:
        red, fill = _seg_max, -_I64_MAX - 1
    tie = nonnull
    bests = []
    for i in range(L):
        limb = data[..., i]
        gid_tie = jnp.where(tie, gid_nn, n)
        best = red(jnp.where(tie, limb, fill), gid_tie, n + 1)[:n]
        bests.append(best)
        tie = tie & (limb == best[jnp.clip(gid_nn, 0, n - 1)])
    return [jnp.stack(bests, axis=-1)]


# ---------------------------------------------------------------------------
# group id assignment
# ---------------------------------------------------------------------------

def _mix64(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _key_codes(datas, valids, domains):
    """Per-column null-aware codes (0 = NULL), plus cardinalities."""
    codes, cards = [], []
    for (d, v), dom in zip(zip(datas, valids), domains):
        lo, hi = dom
        code = jnp.where(v, d.astype(jnp.int64) - lo + 1, 0)
        codes.append(code)
        cards.append(int(hi - lo + 2))
    return codes, cards


def pack_or_hash_keys(datas, valids, domains) -> Tuple[jax.Array, bool]:
    """Combine key columns into one integer key. Exact packing when
    domains fit 63 bits (always true for TPC-H keys); else 64-bit mix
    (collision odds ~ n^2/2^65 — the planner can demand exactness by
    supplying domains).

    TPU dtype note: packed keys narrow to int32 when the domain product
    fits 31 bits — int64 is emulated on TPU (v5e has no native 64-bit
    lanes), so narrow keys make the downstream sorts/searches/scatters
    run at native width."""
    if not datas:
        return None, True
    if any(d.ndim > 1 for d in datas):
        # raw-varchar keys fold through a byte hash lane; long-decimal
        # limbs have no safe hash-collision semantics for decimals
        from presto_tpu.ops.rawstring import hash_bytes

        lanes = []
        for d, v in zip(datas, valids):
            if d.ndim > 1 and d.dtype == jnp.uint8:
                lanes.append((hash_bytes(d), v))
            elif d.ndim > 1:
                raise ValueError(
                    "long-decimal grouping/join keys unsupported (cast to "
                    "a shorter decimal or double)")
            else:
                lanes.append((d, v))
        h = jnp.zeros(datas[0].shape[0], dtype=jnp.uint64)
        for d, v in lanes:
            lane = jnp.where(v, d.astype(jnp.int64), 0).astype(jnp.uint64)
            h = _mix64(h ^ _mix64(lane + jnp.uint64(0x9E37) * v.astype(jnp.uint64)))
        return h.astype(jnp.int64) & jnp.int64(0x7FFFFFFFFFFFFFFF), False
    if domains is not None and all(d is not None for d in domains):
        codes, cards = _key_codes(datas, valids, domains)
        prod = 1
        for c in cards:
            prod *= c
        if prod < (1 << 62):
            key = jnp.zeros_like(codes[0])
            for code, card in zip(codes, cards):
                key = key * card + code
            if prod < (1 << 31):
                key = key.astype(jnp.int32)
            return key, True
    h = jnp.zeros(datas[0].shape, dtype=jnp.uint64)
    for d, v in zip(datas, valids):
        # NULLs must hash identically regardless of residual data: zero
        # the data lane and fold the null flag in separately.
        lane = jnp.where(v, d.astype(jnp.int64), 0).astype(jnp.uint64)
        h = _mix64(h ^ _mix64(lane + jnp.uint64(0x9E37) * v.astype(jnp.uint64)))
    return h.astype(jnp.int64) & jnp.int64(0x7FFFFFFFFFFFFFFF), False


@dataclasses.dataclass(frozen=True)
class _SortCtx:
    """Run geometry of rows that arrive grouped (equal keys contiguous:
    a sorted batch, ``_sorted_runs``, or a streaming input,
    ``_presorted_group_ids``), enabling large-G segment sums as
    cumsum + boundary difference over the values in the order they
    already have: no gather of rows and no XLA scatter-add (scatter
    serializes on TPU and compiles pathologically slowly at big shapes;
    cumsum is one vector pass).

    starts: (max_groups,) position of each group's first row
    ends:   (max_groups,) position of each group's last row
    group_live: (max_groups,) group index < num_groups
    """

    starts: jax.Array
    ends: jax.Array
    group_live: jax.Array

    def sum(self, vals: jax.Array, gid: jax.Array, n: int) -> jax.Array:
        """Per-group sums for groups 0..n-1; rows with gid >= n (dead /
        filtered / null per this aggregate) contribute zero."""
        dead = gid >= n
        if vals.ndim > 1:
            dead = dead[:, None]
            glive = self.group_live[:, None]
        else:
            glive = self.group_live
        # the masked values feed the cumsum only: a group's sum is the
        # running sum at its end less the one before its start
        cs = jnp.cumsum(jnp.where(dead, jnp.zeros_like(vals), vals), axis=0)
        ends = jnp.clip(self.ends, 0, cs.shape[0] - 1)
        before = self.starts - 1
        lead = jnp.take(cs, jnp.clip(before, 0, cs.shape[0] - 1), axis=0)
        if cs.ndim > 1:
            before = before[:, None]
        seg = (jnp.take(cs, ends, axis=0)
               - jnp.where(before >= 0, lead, jnp.zeros_like(lead)))
        return jnp.where(glive, seg, jnp.zeros_like(seg))


def _runs(first: jax.Array, live: jax.Array, max_groups: int, end):
    """Group ids, group count and run geometry from the run-start flags
    of rows that arrive grouped.  Groups past ``max_groups`` (truncation)
    take id ``max_groups`` like dead rows; the first ``max_groups``
    keep their exact bounds.  ``end``: one past the last row that can
    belong to a group."""
    gid_raw = jnp.cumsum(first.astype(jnp.int32)) - 1
    gid = jnp.where(live, jnp.minimum(gid_raw, max_groups), max_groups)
    num_groups = jnp.sum(first.astype(jnp.int32))
    # the first row of each group, and of the first group past the
    # capacity (where the last kept group ends): a scatter of
    # max_groups + 1 entries
    slot = jnp.where(first & (gid_raw <= max_groups), gid_raw, max_groups + 1)
    idx = jnp.arange(first.shape[0], dtype=jnp.int32)
    starts = (jnp.zeros(max_groups + 2, dtype=jnp.int32)
              .at[slot].set(idx, mode="drop"))[:max_groups + 1]
    g = jnp.arange(max_groups, dtype=jnp.int32)
    next_start = jnp.where(g + 1 < num_groups, starts[1:], end)
    ctx = _SortCtx(starts=starts[:max_groups], ends=next_start - 1,
                   group_live=g < num_groups)
    return gid, num_groups, ctx


def _to_int64(x: jax.Array) -> jax.Array:
    """``x`` as an int64 lane of the sort's stack, losslessly: floats by
    their bits, narrower integers sign- or zero-extended."""
    if x.dtype == jnp.int64:
        return x
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"int{8 * x.dtype.itemsize}"))
    elif x.dtype.itemsize == 8:
        return jax.lax.bitcast_convert_type(x, jnp.int64)
    return x.astype(jnp.int64)


def _from_int64(y: jax.Array, dtype) -> jax.Array:
    """``_to_int64``'s inverse."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.int64:
        return y
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(
            y.astype(f"int{8 * dtype.itemsize}"), dtype)
    if dtype.itemsize == 8:
        return jax.lax.bitcast_convert_type(y, dtype)
    return y.astype(dtype)


@jax.named_scope("agg:sort")
def _sorted_runs(key_live: jax.Array, lanes: Sequence[jax.Array],
                 max_groups: int, zero=None):
    """THE sort of the sort path: the live-masked key (dead rows hold
    the dtype's maximum and sort last) and the batch's lanes in one
    stable ``lax.sort``, so the lanes arrive in group order and nothing
    is fetched after it.  From ``_STACK_FROM`` lanes on they ride as the
    rows of one int64 stack, each beside its own copy of the key, so the
    sort is one kernel whatever their number: the TPU's sort code grows
    with every operand it carries, and stays in HBM as long as its
    program is loaded (``_SORT_STACK_BYTES``).  ``zero``:
    ``_zero_after``'s, added to the key and the lanes.  Returns (sorted
    key, sorted lanes, live rows, group ids, group count, _SortCtx), all
    in sorted order."""
    sentinel = jnp.iinfo(key_live.dtype).max
    stacked = len(lanes) >= _STACK_FROM
    ins = [_to_int64(x) if stacked or zero is not None else x for x in lanes]
    if zero is not None:
        key_live = key_live + zero.astype(key_live.dtype)
        ins = [x + zero.astype(jnp.int64) for x in ins]
    if stacked:
        stack = jnp.stack(ins)
        keys, stack = jax.lax.sort(
            (jnp.broadcast_to(key_live, stack.shape), stack),
            dimension=1, num_keys=1, is_stable=True)
        sk, outs = keys[0], list(stack)
    else:
        sk, *outs = jax.lax.sort((key_live, *ins), num_keys=1, is_stable=True)
    out = [y if y.dtype == x.dtype else _from_int64(y, x.dtype)
           for y, x in zip(outs, lanes)]
    live = sk != sentinel
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), sk[1:] != sk[:-1]]) & live
    gid, num_groups, ctx = _runs(first, live, max_groups,
                                 jnp.sum(live.astype(jnp.int32)))
    return sk, out, live, gid, num_groups, ctx


@jax.named_scope("agg:sort")
def _presorted_group_ids(key: jax.Array, live: jax.Array, max_groups: int):
    """Streaming-aggregation grouping (StreamingAggregationOperator.java:38
    analog): input rows arrive grouped (equal keys contiguous), so run
    boundaries come from comparing each live row with the previous LIVE
    row (cummax forward-fill skips filtered holes) — no sort at all.
    Returns (gid, num_groups, each group's first row, _SortCtx), the
    reductions walking the rows in their own order."""
    rows = key.shape[0]
    idx = jnp.arange(rows, dtype=jnp.int32)
    last_live = jax.lax.cummax(jnp.where(live, idx, -1))
    prev_live = jnp.concatenate([jnp.full(1, -1, jnp.int32), last_live[:-1]])
    prev_key = key[jnp.clip(prev_live, 0, rows - 1)]
    first = live & ((prev_live < 0) | (prev_key != key))
    gid, num_groups, ctx = _runs(first, live, max_groups, rows)
    return gid, num_groups, ctx.starts, ctx


# ---------------------------------------------------------------------------
# the payload that rides the key sort
# ---------------------------------------------------------------------------

#: the most bytes one sort's stack of lanes holds, in and out: each lane
#: a row costs its int64 and its copy of the key, twice (32 bytes).
#: More lanes go through further sorts of the same key (``_batches``).
#: A further sort costs its code, 3.6-6.0 MB of HBM at 2^15-2^17 rows
#: on a v5e (one to nine lanes beside an int64 key), for as long as its
#: program is loaded and in every loaded program at once; a lane costs
#: its 32 bytes a row only while its sort runs.  So a page of up to
#: 2^17 rows sorts 16 lanes at once, one of 2^21 rows or more one.
_SORT_STACK_BYTES = 1 << 26

#: the fewest lanes that ride as a stack; fewer ride as operands of
#: their own beside the one key.  A stack sorts its key once a lane;
#: its code is about that of two int64 operands (2^17 rows: the key
#: with one int64 and one int32 operand 5.43 MB, a stack of one 5.17,
#: of five 6.04), and each further operand adds 0.9 MB an int32 word
_STACK_FROM = 3


def _lanes_per_sort(rows: int) -> int:
    return max(1, _SORT_STACK_BYTES // (32 * rows))


#: aggregates whose partial states read their second argument
_ARG2_FNS = frozenset(COVAR_FNS) | {
    "min_by", "max_by", "evaluate_classifier_predictions",
    "learn_regressor", "learn_classifier", "map_agg", "hll_sketch",
    "multimap_agg", "max_by_n", "min_by_n"}

# this thread's sort-path aggregations and their sorts, counted while
# they are traced, never reset (``traced_sorted_runs``)
_TRACED = threading.local()


def traced_sorted_runs() -> Tuple[int, int]:
    """(sort-path aggregations, sorts they run) that this thread has
    traced so far: ``grouped_aggregate``'s sort branch and
    ``merge_aggregate`` with keys, counted once per trace, not per
    call.  A program's counts are the differences across its trace
    (``exec/programs.Program``)."""
    return getattr(_TRACED, "aggs", 0), getattr(_TRACED, "sorts", 0)


def _count_traced(sorts: int) -> None:
    _TRACED.aggs = getattr(_TRACED, "aggs", 0) + 1
    _TRACED.sorts = getattr(_TRACED, "sorts", 0) + sorts


def _ride_inputs(agg: AggCall) -> List[Tuple[Expr, str]]:
    """The expressions an aggregate's partial state reads, each with
    what of it rides the sort: ``"bit"`` (a filter: its value and
    validity as one flag), ``"valid"`` (``count``: the validity only) or
    ``"data"`` (the value and its validity).  ``count_star`` reads
    nothing: the live rows are the sorted key's."""
    out: List[Tuple[Expr, str]] = []
    if agg.filter is not None:
        out.append((agg.filter, "bit"))
    if agg.fn == "count_star":
        return out
    out.append((agg.arg, "valid" if agg.fn == "count" else "data"))
    if agg.fn in _ARG2_FNS:
        out.append((agg.arg2, "data"))
    return out


def _lane_cost(x, long_decimal: bool) -> Tuple[int, int, bool]:
    """(lanes, flag bits, rides the row index) of an array of ``x``'s
    shape and dtype (``_Payload.value``'s rule)."""
    if x.ndim == 1:
        if x.dtype == jnp.bool_:
            return 0, 1, False
        return 1, 0, False
    if long_decimal:
        return int(x.shape[-1]), 0, False
    return 0, 0, True


def _width(cost: Tuple[int, int, bool]) -> int:
    """The lanes of the stack: flags 32 to a lane, the row index one."""
    lanes, bits, index = cost
    return lanes + -(-bits // 32) + int(index)


def _add(a, b):
    return a[0] + b[0], a[1] + b[1], a[2] or b[2]


def _batches(costs: Sequence[Tuple[int, int, bool]], per_sort: int,
             first=(0, 0, False)) -> List[List[int]]:
    """Aggregates by sort: in order, each joins the current sort while
    its stack stays within ``per_sort`` lanes (``_lanes_per_sort``),
    else starts the next one (an aggregate wider than that rides
    alone).  ``first``: what the first sort carries besides (the key
    columns, which give the output keys).  Always one sort at least:
    the groups come from it."""
    out: List[List[int]] = [[]]
    acc = first
    for i, cost in enumerate(costs):
        nxt = _add(acc, cost)
        if out[-1] and _width(nxt) > per_sort:
            out.append([])
            nxt = cost
        out[-1].append(i)
        acc = nxt
    return out


class _Payload:
    """One sort's payload, built in row order: a lane per value (a long
    decimal one lane per limb), every flag packed as a bit of an int32
    lane, and the row index for values too wide to ride (containers,
    raw strings), which are then gathered by it.  ``value``/``bit``
    return readers of the sorted batch."""

    def __init__(self, rows: int):
        self.rows = rows
        self.lanes: List[jax.Array] = []
        self.bits: List[jax.Array] = []
        self.index = False

    def _lane(self, x):
        self.lanes.append(x)
        i = len(self.lanes) - 1
        return lambda s: s[0][i]

    def bit(self, mask):
        self.bits.append(mask)
        j = len(self.bits) - 1
        return lambda s: ((s[1][j // 32] >> (j % 32)) & 1) != 0

    def value(self, x, long_decimal: bool):
        _, bits, index = _lane_cost(x, long_decimal)
        if bits:
            return self.bit(x)
        if not index:
            if x.ndim == 1:
                return self._lane(x)
            parts = [self._lane(x[:, j]) for j in range(x.shape[-1])]
            return lambda s: jnp.stack([p(s) for p in parts], axis=-1)
        self.index = True
        return lambda s: jnp.take(x, s[2], axis=0)

    def operands(self) -> List[jax.Array]:
        flags = []
        for k in range(0, len(self.bits), 32):
            f = jnp.zeros(self.rows, jnp.int32)
            for j, m in enumerate(self.bits[k:k + 32]):
                f = f | (m.astype(jnp.int32) << j)
            flags.append(f)
        index = [jnp.arange(self.rows, dtype=jnp.int32)] if self.index else []
        return self.lanes + flags + index

    def sorted(self, out: Sequence[jax.Array]):
        n = len(self.lanes)
        nf = -(-len(self.bits) // 32)
        return (out[:n], out[n:n + nf], out[n + nf] if self.index else None)


class _SortedInputs:
    """``ExprCompiler``'s stand-in over a sorted batch: ``compile(e)``
    gives ``e``'s (data, valid) in sorted order, as they came out of
    the sort (``_partial_states`` then reduces in that order)."""

    def __init__(self, table: Dict[int, tuple]):
        self._table = table

    def compile(self, expr):
        dv = self._table[id(expr)]
        return lambda _page: dv


@dataclasses.dataclass
class _SortedPage:
    """What ``_partial_states`` reads of a page besides its inputs:
    the live rows (sorted order) and the blocks' dictionaries."""

    blocks: tuple
    row_mask: jax.Array


def _zero_after(done) -> jax.Array:
    """An int32 zero that XLA cannot fold, computed from the states in
    ``done``.  Added to the next sort's key and stack, it keeps that
    sort after ``done``'s reductions and apart from the sorts before it:
    the compiler drops an ``optimization_barrier`` before it merges
    sorts of equal keys back into one, lanes and all."""
    s = jnp.zeros((), jnp.int64)
    for x in jax.tree_util.tree_leaves(done):
        x = x.reshape(-1)[0]
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)
        s = s + x.astype(jnp.int64)
    return (s == s + 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# main kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """Static description of a grouped aggregation's output page:
    group-key blocks then one block per state column (partial) or per
    aggregate (final/single)."""

    num_keys: int
    aggs: Tuple[AggCall, ...]
    mode: str  # single | partial | final


def grouped_aggregate(
    page: Page,
    group_exprs: Sequence[Expr],
    aggs: Sequence[AggCall],
    max_groups: int,
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    mode: str = "single",
    return_count: bool = False,
    presorted: bool = False,
    proven: Optional[Sequence[bool]] = None,
    lane_rows: Optional[Sequence[Optional[int]]] = None,
) -> Page:
    """Aggregate ``page`` by ``group_exprs``.  With ``presorted=True``
    the input is promised to arrive with equal group keys contiguous
    (streaming aggregation) and grouping skips the argsort.

    ``proven`` and ``lane_rows`` are what the plan's intervals proved
    (``exec/chain.AggPartial``): one outcome per guarded arithmetic
    site of ``agg_exprs(group_exprs, aggs)``, and per aggregate the
    page capacity up to which its sum fits one int64 lane
    (``one_lane_sums`` decides with it).  Absent: every guard, and
    per-row limbs.

    mode='single' emits finalized values; 'partial' emits state columns
    (for exchange + merge_aggregate).

    The output page has ``max_groups`` slots; on the sort path
    ``min(max_groups, page.capacity)``, since a page has no more groups
    than rows.

    Overflow: if the input has more than ``max_groups`` distinct keys
    the output is silently truncated to the first ``max_groups`` groups
    in key order — pass ``return_count=True`` to get (page, num_groups)
    so the driver can detect ``num_groups > max_groups`` and re-plan
    with a larger capacity (the reference instead rehashes:
    MultiChannelGroupByHash.java:138-145 tryRehash).
    """
    c = ExprCompiler.for_page(page, proven=proven_sites(
        agg_exprs(group_exprs, aggs), proven))
    one_lane = one_lane_sums(aggs, lane_rows,
                             max_groups if group_exprs else 1, page.capacity)
    kd = [c.compile(e)(page) for e in group_exprs]
    key_dicts = expr_key_dicts(page, group_exprs)
    datas = canonicalize_codes([d for d, _ in kd], key_dicts)
    valids = [v for _, v in kd]
    kd = list(zip(datas, valids))  # rep rows must carry canonical codes
    agg_dicts = [_agg_dict(a, [b.dictionary for b in page.blocks])
                 for a in aggs]

    live = page.row_mask

    if not group_exprs:
        # global aggregation: one group
        gid = jnp.where(live, 0, 1)
        states = _partial_states(page, aggs, gid, 1, c=c, one_lane=one_lane)
        key_blocks: List[Block] = []
        out_mask = jnp.ones(1, dtype=jnp.bool_)
        out = _emit(key_blocks, states, aggs, out_mask, mode, group_exprs, key_dicts, agg_dicts)
        return (out, jnp.ones((), jnp.int32)) if return_count else out

    key, exact = pack_or_hash_keys(datas, valids, key_domains)

    if presorted:
        # streaming path: run boundaries from the input order itself
        gid, num_groups, rep_rows, ctx = _presorted_group_ids(key, live, max_groups)
        states = _partial_states(page, aggs, gid, max_groups, ctx=ctx, c=c,
                                 one_lane=one_lane)
        key_blocks = []
        for (d, v), e, dic in zip(kd, group_exprs, key_dicts):
            key_blocks.append(Block(d[rep_rows].astype(e.type.np_dtype),
                                    v[rep_rows], e.type, dic))
        out_mask = jnp.arange(max_groups) < num_groups
        out = _emit(key_blocks, states, aggs, out_mask, mode, group_exprs,
                    key_dicts, agg_dicts)
        return (out, num_groups) if return_count else out

    # packed-direct: group id == packed key, no sort; output capacity is
    # always max_groups (padded above prod) so downstream shapes match
    # the sort path.
    if exact and key_domains is not None and all(d is not None for d in key_domains):
        _, cards = _key_codes(datas, valids, key_domains)
        prod = 1
        for card in cards:
            prod *= card
        if prod <= min(max_groups, DIRECT_GROUP_LIMIT):
            gid = jnp.where(live, key, max_groups)
            states = _partial_states(page, aggs, gid, max_groups, c=c,
                                     one_lane=one_lane)
            present = _seg_sum(live.astype(jnp.int64), gid, max_groups + 1)[:max_groups] > 0
            key_blocks = _unpack_key_blocks(
                cards, key_domains, group_exprs, key_dicts, prod, max_groups
            )
            out = _emit(key_blocks, states, aggs, present, mode, group_exprs, key_dicts, agg_dicts)
            return (out, jnp.sum(present.astype(jnp.int32))) if return_count else out

    # sort path.  A page has no more groups than rows: where the
    # capacity asked for is larger (a chain that compacted its page,
    # tiny splits), the group-side arrays and the output page take the
    # page's, and such a page cannot truncate.  Not above: on the
    # packed-direct path the slot IS the key.
    max_groups = min(max_groups, page.capacity)
    inputs = [_ride_inputs(a) for a in aggs]
    fns = {id(e): c.compile(e) for ins in inputs for e, _ in ins}
    shapes = jax.eval_shape(
        lambda p: {k: f(p)[0] for k, f in fns.items()}, page)

    def cost(i):
        out = (0, 0, False)
        for e, what in inputs[i]:
            out = _add(out, (0, 1, False))
            if what == "data":
                out = _add(out, _lane_cost(shapes[id(e)],
                                           e.type.is_long_decimal))
        return out

    def ride(i, p, payload, table):
        for e, what in inputs[i]:
            if id(e) in table:
                continue
            d, v = fns[id(e)](p)
            if what == "bit":
                m = payload.bit(d & v)
                table[id(e)] = (m, m)
            elif what == "valid":
                table[id(e)] = (None, payload.bit(v))
            else:
                table[id(e)] = (payload.value(_rows(d, p.capacity),
                                              e.type.is_long_decimal),
                                payload.bit(v))

    def reduce(batch, table, live_s, gid_s, ctx):
        return _partial_states(
            _SortedPage(page.blocks, live_s), [aggs[i] for i in batch],
            gid_s, max_groups, ctx=ctx, c=_SortedInputs(table),
            one_lane=[one_lane[i] for i in batch] if one_lane else ())

    def rekey(p):
        kd_p = [c.compile(e)(p) for e in group_exprs]
        return pack_or_hash_keys(canonicalize_codes(
            [d for d, _ in kd_p], key_dicts), [v for _, v in kd_p],
            key_domains)[0]

    states, key_blocks, num_groups, out_mask = _ride_the_sort(
        page, key, rekey, kd, [e.type for e in group_exprs], key_dicts,
        key_domains[0][0] if exact and len(kd) == 1 else None,
        [cost(i) for i in range(len(aggs))], ride, reduce, max_groups)
    out = _emit(key_blocks, states, aggs, out_mask, mode, group_exprs, key_dicts, agg_dicts)
    return (out, num_groups) if return_count else out


def _rows(x: jax.Array, rows: int) -> jax.Array:
    """``x`` as a lane: a scalar broadcast to the page's rows."""
    return jnp.broadcast_to(x, (rows,)) if x.ndim == 0 else x


def _ride_the_sort(page, key, rekey, key_cols, key_types, key_dicts,
                   key_lo, costs, ride, reduce, max_groups: int):
    """The sort path of ``grouped_aggregate`` and ``merge_aggregate``:
    the aggregates' inputs ride sorts of the key in batches
    (``_batches``), each batch reduced to its ``(max_groups,)`` states
    before the next one's row-order lanes are built (``_zero_after``).

    ``key``: the row-order packed or hashed key, ``rekey(page)`` the
    same for a later batch; ``key_cols``: the row-order key columns,
    which ride the first sort to give the output keys (never unpacked
    from a packed key: the TPU divides an int64 in software, 7 MB of
    code for TPC-H q3's three keys).  ``key_lo``: where the key is one
    column packed exactly, its domain's low end (``_key_codes``: the
    packed key is the value less ``key_lo`` plus one, 0 for NULL); the
    output key then comes from the sorted key, by a subtraction, and
    the column does not ride.  ``costs[i]``: what
    aggregate ``i`` puts on a sort (``_lane_cost``); ``ride(i, page,
    payload, table)`` puts it there, ``table`` mapping each input to
    readers of the sorted batch; ``reduce(batch, table, live, gid,
    ctx)`` gives the batch's states from what ``table`` read.  Returns
    (states, key blocks, group count, live groups)."""
    rows = page.capacity
    if key_lo is not None:
        key_cols = []
    first = (0, 0, False)
    for d, _ in key_cols:
        first = _add(_add(first, _lane_cost(d, False)), (0, 1, False))
    batches = _batches(costs, _lanes_per_sort(rows), first)
    _count_traced(len(batches))
    states: List[Optional[list]] = [None] * len(costs)
    done = None
    for b, batch in enumerate(batches):
        k = key if b == 0 else rekey(page)
        key_live = jnp.where(page.row_mask, k,
                             jnp.asarray(jnp.iinfo(k.dtype).max, k.dtype))
        payload = _Payload(rows)
        keys = [(payload.value(_rows(d, rows), False), payload.bit(v))
                for d, v in key_cols] if b == 0 else []
        table: Dict[int, tuple] = {}
        for i in batch:
            ride(i, page, payload, table)
        sk, out, live_s, gid_s, ng, ctx = _sorted_runs(
            key_live, payload.operands(), max_groups,
            None if done is None else _zero_after(done))
        s = payload.sorted(out)
        read = {k_: tuple(r if r is None else r(s) for r in rs)
                for k_, rs in table.items()}
        part = reduce(batch, read, live_s, gid_s, ctx)
        for i, st in zip(batch, part):
            states[i] = st
        done = part
        if b == 0:
            num_groups, out_mask = ng, ctx.group_live
            if key_lo is None:
                key_blocks = [
                    Block(jnp.take(dr(s), ctx.starts, axis=0).astype(t.np_dtype),
                          jnp.take(vr(s), ctx.starts, axis=0) & out_mask,
                          t, dic)
                    for (dr, vr), t, dic in zip(keys, key_types, key_dicts)]
            else:
                code = jnp.take(sk, ctx.starts).astype(jnp.int64)
                key_blocks = [Block((code - 1 + key_lo).astype(t.np_dtype),
                                    (code > 0) & out_mask, t, dic)
                              for t, dic in zip(key_types, key_dicts)]
    return states, key_blocks, num_groups, out_mask


def _unpack_key_blocks(cards, domains, group_exprs, key_dicts, prod, capacity) -> List[Block]:
    gids = jnp.arange(capacity, dtype=jnp.int64)
    in_range = gids < prod
    blocks = []
    stride = prod
    for card, (lo, _), e, dic in zip(cards, domains, group_exprs, key_dicts):
        stride //= card
        code = (gids // stride) % card
        data = (code - 1 + lo).astype(e.type.np_dtype)
        blocks.append(Block(data, (code > 0) & in_range, e.type, dic))
    return blocks


def _emit(key_blocks, states, aggs, out_mask, mode, group_exprs, key_dicts,
          agg_dicts=None) -> Page:
    agg_dicts = agg_dicts or [None] * len(aggs)
    if mode == "partial":
        blocks = list(key_blocks)
        for agg, cols, adict in zip(aggs, states, agg_dicts):
            for j, (t, colv) in enumerate(zip(state_types(agg), cols)):
                blocks.append(Block(colv.astype(t.np_dtype), out_mask, t,
                                    adict if j == 0 else None))
        return Page(tuple(blocks), out_mask)
    agg_blocks = _finalize(states, aggs, agg_dicts)
    # clamp validity to live groups
    agg_blocks = [Block(b.data, b.valid & out_mask, b.type, b.dictionary) for b in agg_blocks]
    return Page(tuple(key_blocks) + tuple(agg_blocks), out_mask)


def merge_aggregate(
    partial: Page,
    num_keys: int,
    aggs: Sequence[AggCall],
    max_groups: int,
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    mode: str = "single",
    return_count: bool = False,
) -> Page:
    """Final aggregation over a page of partial states (group keys in
    the first ``num_keys`` blocks, then state columns in
    ``state_types`` order).

    With ``return_count=True`` returns (page, num_groups) so callers can
    detect ``num_groups > max_groups`` truncation and retry larger —
    the distributed counterpart of LocalRunner._check_overflow."""
    live = partial.row_mask
    key_dicts = [partial.blocks[i].dictionary for i in range(num_keys)]
    datas = canonicalize_codes(
        [partial.blocks[i].data for i in range(num_keys)], key_dicts)
    valids = [partial.blocks[i].valid for i in range(num_keys)]
    key_types = [partial.blocks[i].type for i in range(num_keys)]

    # slice state columns per agg; the first state column carries the
    # dictionary for value-preserving aggregates (min/max/min_by/max_by)
    state_cols: List[List[jax.Array]] = []
    agg_dicts: List[Optional[object]] = []
    pos = num_keys
    for agg in aggs:
        ncols = len(state_types(agg))
        state_cols.append([partial.blocks[pos + j].data for j in range(ncols)])
        agg_dicts.append(partial.blocks[pos].dictionary)
        pos += ncols

    from presto_tpu.expr.ir import ColumnRef

    group_exprs = [
        ColumnRef(type=key_types[i], index=i) for i in range(num_keys)
    ]

    if num_keys == 0:
        gid = jnp.where(live, 0, 1).astype(jnp.int32)
        merged = _merge_states(state_cols, aggs, gid, 1)
        out = _emit([], merged, aggs, jnp.ones(1, jnp.bool_), mode, group_exprs, key_dicts, agg_dicts)
        return (out, jnp.ones((), jnp.int32)) if return_count else out

    key, exact = pack_or_hash_keys(datas, valids, key_domains)
    starts = [num_keys + sum(len(state_types(a)) for a in aggs[:i])
              for i in range(len(aggs))]

    def cost(i):
        out = (0, 0, False)
        for col, t in zip(state_cols[i], state_types(aggs[i])):
            out = _add(out, _lane_cost(col, t.is_long_decimal))
        return out

    def ride(i, p, payload, table):
        table[i] = tuple(
            payload.value(p.blocks[starts[i] + j].data, t.is_long_decimal)
            for j, t in enumerate(state_types(aggs[i])))

    def reduce(batch, table, live_s, gid_s, ctx):
        return _merge_states([list(table[i]) for i in batch],
                             [aggs[i] for i in batch], gid_s, max_groups,
                             ctx=ctx)

    def rekey(p):
        return pack_or_hash_keys(canonicalize_codes(
            [p.blocks[i].data for i in range(num_keys)], key_dicts),
            [p.blocks[i].valid for i in range(num_keys)], key_domains)[0]

    merged, key_blocks, num_groups, out_mask = _ride_the_sort(
        partial, key, rekey, list(zip(datas, valids)), key_types,
        key_dicts, key_domains[0][0] if exact and num_keys == 1 else None,
        [cost(i) for i in range(len(aggs))], ride, reduce, max_groups)
    out = _emit(key_blocks, merged, aggs, out_mask, mode, group_exprs, key_dicts, agg_dicts)
    return (out, num_groups) if return_count else out
