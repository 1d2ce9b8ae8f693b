import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.expr.ir import AggCall, call, col, lit
from presto_tpu.ops import (
    build_join,
    filter_page,
    grouped_aggregate,
    limit_page,
    merge_aggregate,
    probe_expand,
    probe_join,
    project_page,
    sort_page,
    topn_page,
)
from presto_tpu.page import Dictionary, Page
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR, DecimalType


def rows(page):
    return page.to_pylist()


# ---------------------------------------------------------------------------
# filter / project
# ---------------------------------------------------------------------------

def test_filter_project():
    p = Page.from_arrays(
        [np.arange(10, dtype=np.int64), np.arange(10, dtype=np.float64) * 1.5],
        [BIGINT, DOUBLE],
    )
    f = filter_page(p, call("lt", col(0, BIGINT), lit(5, BIGINT)))
    assert int(f.num_rows()) == 5
    pr = project_page(f, [call("mul", col(1, DOUBLE), lit(2.0, DOUBLE))])
    assert [r[0] for r in rows(pr)] == [0.0, 3.0, 6.0, 9.0, 12.0]


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def _agg_page():
    # group col (3 distinct), value col with one NULL
    g = np.array([2, 1, 2, 1, 0, 2, 1, 2], dtype=np.int64)
    v = np.array([10, 20, 30, 40, 50, 60, 70, 80], dtype=np.int64)
    valid = np.array([True] * 7 + [False])
    return Page.from_arrays([g, v], [BIGINT, BIGINT], valids=[None, valid])


def _expected():
    # g=0: [50]; g=1: [20,40,70]; g=2: [10,30,60,(null)]
    return {
        0: dict(count=1, sum=50, mn=50, mx=50, cstar=1),
        1: dict(count=3, sum=130, mn=20, mx=70, cstar=3),
        2: dict(count=3, sum=100, mn=10, mx=60, cstar=4),
    }


AGGS = [
    AggCall("sum", col(1, BIGINT), BIGINT),
    AggCall("count", col(1, BIGINT), BIGINT),
    AggCall("count_star", None, BIGINT),
    AggCall("min", col(1, BIGINT), BIGINT),
    AggCall("max", col(1, BIGINT), BIGINT),
    AggCall("avg", col(1, BIGINT), DOUBLE),
]


@pytest.mark.parametrize("domains", [None, [(0, 2)]])
def test_grouped_aggregate(domains):
    p = _agg_page()
    out = grouped_aggregate(p, [col(0, BIGINT)], AGGS, max_groups=16, key_domains=domains)
    got = {r[0]: r[1:] for r in rows(out)}
    exp = _expected()
    assert set(got) == set(exp)
    for g, (s, c, cs, mn, mx, avg) in got.items():
        e = exp[g]
        assert (s, c, cs, mn, mx) == (e["sum"], e["count"], e["cstar"], e["mn"], e["mx"])
        assert avg == pytest.approx(e["sum"] / e["count"])


@pytest.mark.parametrize("mode", ["single", "partial"])
@pytest.mark.parametrize("path", ["sort", "packed_direct"])
def test_a_page_has_no_more_groups_than_rows(path, mode):
    """On the sort path a capacity over the page's is the page's: the
    same groups as at a capacity that fits, in a page of 8 slots, and
    the count with them.  The packed-direct layout keeps the capacity
    it was given: there the slot is the key."""
    p = _agg_page()
    assert p.capacity == 8
    domains = [(0, 2)] if path == "packed_direct" else None
    big, n_big = grouped_aggregate(
        p, [col(0, BIGINT)], AGGS, max_groups=64, key_domains=domains,
        mode=mode, return_count=True)
    fit, n_fit = grouped_aggregate(
        p, [col(0, BIGINT)], AGGS, max_groups=8, key_domains=domains,
        mode=mode, return_count=True)
    assert sorted(rows(big)) == sorted(rows(fit)) and len(rows(big)) == 3
    assert int(n_big) == int(n_fit) == 3
    assert fit.capacity == 8
    assert big.capacity == (8 if path == "sort" else 64)
    if mode == "single":
        assert {r[0]: r[1] for r in rows(big)} == {
            g: e["sum"] for g, e in _expected().items()}


def test_global_aggregate():
    p = _agg_page()
    out = grouped_aggregate(p, [], AGGS, max_groups=1)
    (r,) = rows(out)
    assert r == (280, 7, 8, 10, 70, pytest.approx(280 / 7))


def test_grouped_aggregate_decimal_and_null_group():
    dec = DecimalType(12, 2)
    g = np.array([1, 1, 2, 2], dtype=np.int64)
    gvalid = np.array([True, True, False, False])  # group NULL bucket
    v = np.array([150, 250, 100, 300], dtype=np.int64)
    p = Page.from_arrays([g, v], [BIGINT, dec], valids=[gvalid, None])
    out = grouped_aggregate(
        p, [col(0, BIGINT)], [AggCall("sum", col(1, dec), dec)], max_groups=8,
        key_domains=[(1, 2)],
    )
    got = {r[0]: r[1] for r in rows(out)}
    assert got == {1: 4.0, None: 4.0}


def test_partial_final_split():
    p = _agg_page()
    # split page into two halves, partial-agg each, then merge
    m1 = np.zeros(8, bool); m1[:4] = True
    m2 = np.zeros(8, bool); m2[4:] = True
    p1 = Page(p.blocks, jnp.asarray(m1) & p.row_mask)
    p2 = Page(p.blocks, jnp.asarray(m2) & p.row_mask)
    pa1 = grouped_aggregate(p1, [col(0, BIGINT)], AGGS, max_groups=8, mode="partial")
    pa2 = grouped_aggregate(p2, [col(0, BIGINT)], AGGS, max_groups=8, mode="partial")
    from presto_tpu.page import concat_pages_host

    merged_in = concat_pages_host([pa1, pa2])
    out = merge_aggregate(merged_in, 1, AGGS, max_groups=8)
    got = {r[0]: r[1:] for r in rows(out)}
    exp = _expected()
    for g, (s, c, cs, mn, mx, avg) in got.items():
        e = exp[g]
        assert (s, c, cs, mn, mx) == (e["sum"], e["count"], e["cstar"], e["mn"], e["mx"])


def test_packed_direct_multikey():
    # two small-domain keys -> direct path, no sort
    a = np.array([0, 1, 0, 1, 0], dtype=np.int64)
    b = np.array([5, 5, 6, 6, 5], dtype=np.int64)
    v = np.array([1, 2, 3, 4, 5], dtype=np.int64)
    p = Page.from_arrays([a, b, v], [BIGINT, BIGINT, BIGINT])
    out = grouped_aggregate(
        p,
        [col(0, BIGINT), col(1, BIGINT)],
        [AggCall("sum", col(2, BIGINT), BIGINT)],
        max_groups=16,
        key_domains=[(0, 1), (5, 6)],
    )
    got = {(r[0], r[1]): r[2] for r in rows(out)}
    assert got == {(0, 5): 6, (1, 5): 2, (0, 6): 3, (1, 6): 4}


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def _build_probe():
    build = Page.from_arrays(
        [np.array([10, 20, 30], dtype=np.int64), np.array([1.0, 2.0, 3.0])],
        [BIGINT, DOUBLE],
    )
    probe = Page.from_arrays(
        [np.array([20, 10, 99, 30, 20], dtype=np.int64),
         np.array([5, 6, 7, 8, 9], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    return build, probe


def test_inner_join_unique():
    b, p = _build_probe()
    jb = build_join(b, [col(0, BIGINT)])
    out = probe_join(jb, p, [col(0, BIGINT)], kind="inner", build_output=[1])
    assert sorted(rows(out)) == [(10, 6, 1.0), (20, 5, 2.0), (20, 9, 2.0), (30, 8, 3.0)]


@pytest.mark.parametrize("kind", ["inner", "left"])
def test_probe_join_is_lookup_then_fetch(kind):
    """The two halves a chain may put a compaction between: the lookup
    reads no build row, the fetch nothing of the probe's keys."""
    from presto_tpu.ops.join import probe_fetch, probe_lookup

    b, p = _build_probe()
    jb = build_join(b, [col(0, BIGINT)])
    pos, match, ok = probe_lookup(jb, p, [col(0, BIGINT)])
    assert pos.shape == match.shape == ok.shape == (p.capacity,)
    assert np.asarray(match)[:5].tolist() == [True, True, False, True, True]
    assert np.asarray(ok)[:5].all()
    out = probe_fetch(jb, p, pos, match, kind, [1])
    assert rows(out) == rows(probe_join(jb, p, [col(0, BIGINT)], kind=kind,
                                        build_output=[1]))


def test_left_join_nulls():
    b, p = _build_probe()
    jb = build_join(b, [col(0, BIGINT)])
    out = probe_join(jb, p, [col(0, BIGINT)], kind="left", build_output=[1])
    got = sorted(rows(out))
    assert (99, 7, None) in got and len(got) == 5


def test_semi_anti_join():
    b, p = _build_probe()
    jb = build_join(b, [col(0, BIGINT)])
    semi = probe_join(jb, p, [col(0, BIGINT)], kind="semi")
    assert sorted(r[0] for r in rows(semi)) == [10, 20, 20, 30]
    anti = probe_join(jb, p, [col(0, BIGINT)], kind="anti")
    assert [r[0] for r in rows(anti)] == [99]


def test_null_keys_never_match():
    b = Page.from_arrays(
        [np.array([10, 20], dtype=np.int64)], [BIGINT],
        valids=[np.array([True, False])],
    )
    p = Page.from_arrays(
        [np.array([10, 20], dtype=np.int64)], [BIGINT],
        valids=[np.array([True, False])],
    )
    jb = build_join(b, [col(0, BIGINT)])
    out = probe_join(jb, p, [col(0, BIGINT)], kind="inner", build_output=[])
    assert rows(out) == [(10,)]


def test_expand_join_many_to_many():
    build = Page.from_arrays(
        [np.array([1, 1, 2, 3, 3, 3], dtype=np.int64),
         np.array([100, 101, 200, 300, 301, 302], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    probe = Page.from_arrays(
        [np.array([3, 1, 7], dtype=np.int64), np.array([-1, -2, -3], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    jb = build_join(build, [col(0, BIGINT)])
    out, total = probe_expand(jb, probe, [col(0, BIGINT)], out_capacity=16, build_output=[1])
    assert int(total) == 5
    got = sorted(rows(out))
    assert got == [(1, -2, 100), (1, -2, 101), (3, -1, 300), (3, -1, 301), (3, -1, 302)]
    # left flavor keeps unmatched probe rows
    outl, totall = probe_expand(jb, probe, [col(0, BIGINT)], out_capacity=16, kind="left", build_output=[1])
    assert int(totall) == 6
    assert (7, -3, None) in rows(outl)


def test_expand_join_overflow_reported():
    build = Page.from_arrays([np.zeros(4, dtype=np.int64)], [BIGINT])
    probe = Page.from_arrays([np.zeros(4, dtype=np.int64)], [BIGINT])
    jb = build_join(build, [col(0, BIGINT)])
    out, total = probe_expand(jb, probe, [col(0, BIGINT)], out_capacity=8)
    assert int(total) == 16  # 4x4 — caller must chunk


def test_composite_key_join():
    build = Page.from_arrays(
        [np.array([1, 1, 2], dtype=np.int64), np.array([7, 8, 7], dtype=np.int64),
         np.array([11, 12, 13], dtype=np.int64)],
        [BIGINT, BIGINT, BIGINT],
    )
    probe = Page.from_arrays(
        [np.array([1, 2, 1], dtype=np.int64), np.array([8, 7, 9], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    doms = [(1, 2), (7, 9)]
    jb = build_join(build, [col(0, BIGINT), col(1, BIGINT)], key_domains=doms)
    out = probe_join(jb, probe, [col(0, BIGINT), col(1, BIGINT)], key_domains=doms,
                     kind="inner", build_output=[2])
    assert sorted(rows(out)) == [(1, 8, 12), (2, 7, 13)]


def test_direct_table_join_paths(monkeypatch):
    """The TPU direct-address table (CSR starts over the packed-key
    domain) must agree with the searchsorted fallback on every probe
    flavor; forced on via the A/B override since CPU test runs would
    otherwise gate it off."""
    monkeypatch.setattr("presto_tpu.ops.join._DIRECT_JOIN_RESOLVED", True)
    doms = [(10, 30)]
    b, p = _build_probe()
    jb = build_join(b, [col(0, BIGINT)], key_domains=doms)
    assert jb.starts is not None  # table actually engaged
    out = probe_join(jb, p, [col(0, BIGINT)], key_domains=doms,
                     kind="inner", build_output=[1])
    assert sorted(rows(out)) == [(10, 6, 1.0), (20, 5, 2.0), (20, 9, 2.0), (30, 8, 3.0)]
    outl = probe_join(jb, p, [col(0, BIGINT)], key_domains=doms,
                      kind="left", build_output=[1])
    assert (99, 7, None) in sorted(rows(outl)) and len(rows(outl)) == 5
    semi = probe_join(jb, p, [col(0, BIGINT)], key_domains=doms, kind="semi")
    assert sorted(r[0] for r in rows(semi)) == [10, 20, 20, 30]
    anti = probe_join(jb, p, [col(0, BIGINT)], key_domains=doms, kind="anti")
    assert [r[0] for r in rows(anti)] == [99]

    # many-to-many expansion through the starts table
    build = Page.from_arrays(
        [np.array([1, 1, 2, 3, 3, 3], dtype=np.int64),
         np.array([100, 101, 200, 300, 301, 302], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    probe = Page.from_arrays(
        [np.array([3, 1, 7], dtype=np.int64),
         np.array([-1, -2, -3], dtype=np.int64)],
        [BIGINT, BIGINT],
    )
    edoms = [(1, 7)]
    jb2 = build_join(build, [col(0, BIGINT)], key_domains=edoms)
    assert jb2.starts is not None
    out2, total = probe_expand(jb2, probe, [col(0, BIGINT)], out_capacity=16,
                               key_domains=edoms, build_output=[1])
    assert int(total) == 5
    assert sorted(rows(out2)) == [
        (1, -2, 100), (1, -2, 101), (3, -1, 300), (3, -1, 301), (3, -1, 302)]

    # null keys still never match with the table engaged
    bn = Page.from_arrays(
        [np.array([10, 20], dtype=np.int64)], [BIGINT],
        valids=[np.array([True, False])],
    )
    pn = Page.from_arrays(
        [np.array([10, 20], dtype=np.int64)], [BIGINT],
        valids=[np.array([True, False])],
    )
    jbn = build_join(bn, [col(0, BIGINT)], key_domains=doms)
    outn = probe_join(jbn, pn, [col(0, BIGINT)], key_domains=doms,
                      kind="inner", build_output=[])
    assert rows(outn) == [(10,)]


def test_direct_table_respects_domain_budget(monkeypatch):
    """A tiny build over a huge domain must NOT pay a domain-sized
    sort: the per-row budget falls back to searchsorted."""
    monkeypatch.setattr("presto_tpu.ops.join._DIRECT_JOIN_RESOLVED", True)
    from presto_tpu.ops.join import DIRECT_DOMAIN_MAX

    b, _ = _build_probe()
    jb = build_join(b, [col(0, BIGINT)], key_domains=[(0, DIRECT_DOMAIN_MAX + 5)])
    assert jb.starts is None


# ---------------------------------------------------------------------------
# sort / topn / limit
# ---------------------------------------------------------------------------

def test_sort_multi_key():
    p = Page.from_arrays(
        [np.array([2, 1, 2, 1], dtype=np.int64), np.array([5.0, 6.0, 4.0, 7.0])],
        [BIGINT, DOUBLE],
    )
    out = sort_page(p, [col(0, BIGINT), col(1, DOUBLE)], [True, False])
    assert rows(out) == [(1, 7.0), (1, 6.0), (2, 5.0), (2, 4.0)]


def test_sort_nulls_last_and_dead_rows():
    p = Page.from_arrays(
        [np.array([3, 1, 2], dtype=np.int64)], [BIGINT],
        valids=[np.array([True, False, True])],
    )
    out = sort_page(p, [col(0, BIGINT)], [True])
    assert rows(out) == [(2,), (3,), (None,)]


def test_topn_limit():
    p = Page.from_arrays([np.array([4, 2, 9, 1, 7], dtype=np.int64)], [BIGINT])
    t = topn_page(p, [col(0, BIGINT)], [True], n=3)
    assert rows(t) == [(1,), (2,), (4,)]
    l = limit_page(p, 2)
    assert rows(l) == [(4,), (2,)]


def test_kernels_jit_cleanly():
    p = _agg_page()

    @jax.jit
    def agg(pg):
        return grouped_aggregate(pg, [col(0, BIGINT)], AGGS, max_groups=8)

    out = agg(p)
    assert len(rows(out)) == 3


# unique-direct leg against the sorted build: 120 distinct keys of the
# domain [1, 200]; probes inside it, below it (0 packs to the NULL code,
# -3 below that), above it, NULL, and one key that packs to the probe
# sentinel (the int32 key's max - 1)
_U_DOM = [(1, 200)]
_U_SENTINEL = np.iinfo(np.int32).max - 1


def _unique_build_probe(build_case):
    rng = np.random.default_rng(3)
    keys = rng.permutation(np.arange(1, 201))[:120].astype(np.int64)
    key_valid = np.ones(len(keys), dtype=bool)
    live = np.ones(len(keys), dtype=bool)
    if build_case == "null_key":
        key_valid[7] = False
    elif build_case == "empty":
        live[:] = False
    b = Page.from_arrays([keys, keys * 10], [BIGINT, BIGINT],
                         valids=[key_valid, None])
    b = Page(b.blocks, jnp.asarray(live))
    probe_keys = np.concatenate([
        rng.integers(1, 201, size=300),
        [0, -3, 201, 250, _U_SENTINEL, keys[0], keys[7]]]).astype(np.int64)
    probe_valid = np.ones(len(probe_keys), dtype=bool)
    probe_valid[::37] = False
    p = Page.from_arrays([probe_keys, -np.arange(len(probe_keys))],
                         [BIGINT, BIGINT], valids=[probe_valid, None])
    return b, p


@pytest.mark.parametrize("build_case", ["dense", "null_key", "empty"])
@pytest.mark.parametrize("probe_op", [
    "inner", "left", "semi", "anti", "mark", "semi_null_aware",
    "anti_null_aware", "mark_null_aware", "expand_inner", "expand_left"])
def test_unique_direct_build_matches_sorted(build_case, probe_op):
    """The sort-free unique-build path (rank by domain prefix count,
    one gather of its key -> rank table a probe row) produces the same
    answers as the sorted build, for every probe kind."""
    b, p = _unique_build_probe(build_case)
    jb_u = build_join(b, [col(0, BIGINT)], key_domains=_U_DOM, unique=True)
    assert jb_u.unique_ok is not None and bool(jb_u.unique_ok)
    assert jb_u.rank is not None and jb_u.starts is None
    jb_s = build_join(b, [col(0, BIGINT)], key_domains=_U_DOM)
    assert jb_s.rank is None
    got = []
    for jb in (jb_u, jb_s):
        if probe_op.startswith("expand_"):
            out, total, matched = probe_expand(
                jb, p, [col(0, BIGINT)], out_capacity=p.capacity + 8,
                key_domains=_U_DOM, kind=probe_op[len("expand_"):],
                return_matched=True)
            got.append((rows(out), int(total),
                        np.flatnonzero(np.asarray(matched)).tolist()))
        else:
            kind, _, aware = probe_op.partition("_")
            out = probe_join(jb, p, [col(0, BIGINT)], key_domains=_U_DOM,
                             kind=kind, null_aware=bool(aware))
            got.append(rows(out))
    assert got[0] == got[1]
    if probe_op == "inner":
        # every matched payload is key * 10; an empty build matches none
        assert all(r[2] == r[0] and r[3] == r[0] * 10 for r in got[0])
        assert (len(got[0]) == 0) == (build_case == "empty")


def _gathers_over(jaxpr, n):
    """Gathers in ``jaxpr`` (sub-jaxprs included) whose indices have
    ``n`` rows."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and \
                eqn.invars[1].aval.shape[0] == n:
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _gathers_over(sub, n)
    return count


@pytest.mark.parametrize("leg", ["unique_direct", "sorted_starts"])
def test_lookup_gathers_per_probe_row(leg):
    """The mechanism, pinned: a unique-direct build's lookup is one
    gather a probe row (its key -> rank table); the sorted leg's CSR
    ``starts`` takes two (lo and hi)."""
    from presto_tpu.ops.join import probe_lookup, set_direct_join_override

    b, p = _unique_build_probe("dense")
    set_direct_join_override(True)
    try:
        jb = build_join(b, [col(0, BIGINT)], key_domains=_U_DOM,
                        unique=leg == "unique_direct")
    finally:
        set_direct_join_override(None)
    assert (jb.rank is not None, jb.starts is not None) == (
        (True, False) if leg == "unique_direct" else (False, True))
    assert p.capacity not in (b.capacity, jb.capacity, _U_DOM[0][1] + 1)
    jaxpr = jax.make_jaxpr(lambda jb, p: probe_lookup(
        jb, p, [col(0, BIGINT)], key_domains=_U_DOM))(jb, p)
    assert _gathers_over(jaxpr.jaxpr, p.capacity) == (
        1 if leg == "unique_direct" else 2)


def test_unique_direct_collision_detected():
    import numpy as np

    from presto_tpu.expr.ir import ColumnRef
    from presto_tpu.ops.join import build_join
    from presto_tpu.page import Page
    from presto_tpu.types import BIGINT

    keys = np.array([1, 2, 2, 5], dtype=np.int64)  # broken promise
    b = Page.from_arrays([keys], [BIGINT])
    jb = build_join(b, [ColumnRef(type=BIGINT, index=0)],
                    key_domains=[(1, 5)], unique=True)
    assert jb.unique_ok is not None and not bool(jb.unique_ok)


def test_packed_direct_positional_fold():
    """combine_packed_states merges packed-direct partials ELEMENTWISE
    (slot == group id): sums add, mins/maxes reduce, variance states
    combine via Chan's formula — and finalize_packed emits the result
    without any re-grouping sort."""
    import jax.numpy as jnp

    from presto_tpu.expr.ir import AggCall, ColumnRef
    from presto_tpu.ops.aggregate import (
        combine_packed_states, finalize_packed, grouped_aggregate,
        packed_fold_supported,
    )
    from presto_tpu.page import Block, Page
    from presto_tpu.types import BIGINT, DOUBLE, DecimalType

    key = ColumnRef(type=BIGINT, index=0)
    val = ColumnRef(type=DOUBLE, index=1)
    aggs = [AggCall(fn="sum", arg=val, type=DOUBLE),
            AggCall(fn="min", arg=val, type=DOUBLE),
            AggCall(fn="count_star", arg=None, type=BIGINT),
            AggCall(fn="variance", arg=val, type=DOUBLE)]
    assert packed_fold_supported(aggs)
    # long-decimal min must NOT take the per-limb elementwise path
    assert not packed_fold_supported(
        [AggCall(fn="min", arg=ColumnRef(type=DecimalType(38, 0), index=1),
                 type=DecimalType(38, 0))])

    def page(keys, vals):
        return Page(
            (Block(jnp.asarray(keys, jnp.int64),
                   jnp.ones(len(keys), jnp.bool_), BIGINT),
             Block(jnp.asarray(vals, jnp.float64),
                   jnp.ones(len(vals), jnp.bool_), DOUBLE)),
            jnp.ones(len(keys), jnp.bool_))

    domains = [(0, 3)]
    pa = grouped_aggregate(page([0, 1, 1, 3], [1.0, 2.0, 4.0, 8.0]),
                           [key], aggs, 6, key_domains=domains,
                           mode="partial")
    pb = grouped_aggregate(page([1, 2, 3, 3], [10.0, 20.0, 40.0, 2.0]),
                           [key], aggs, 6, key_domains=domains,
                           mode="partial")
    merged = combine_packed_states(pa, pb, 1, aggs)
    out = finalize_packed(merged, 1, aggs)
    rows = {int(k): (float(s), float(m), int(c))
            for k, s, m, c, _v in out.to_pylist()}
    assert rows[0] == (1.0, 1.0, 1)
    assert rows[1] == (16.0, 2.0, 3)
    assert rows[2] == (20.0, 20.0, 1)
    assert rows[3] == (50.0, 2.0, 3)
    # variance of group 3 values {8, 40, 2}: sample var = 417.3333
    var3 = [r for r in out.to_pylist() if int(r[0]) == 3][0][4]
    assert abs(float(var3) - 417.0 - 1.0 / 3.0) < 1e-6
