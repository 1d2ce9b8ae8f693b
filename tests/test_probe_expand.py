"""The expanding probe's map from output slot to probe row (PR 28): one
scatter and a running maximum where an 18-round binary search stood.
Every live slot of ``probe_expand`` against numpy's ``repeat`` over the
match counts, on both lookup legs, and the lowered text of the program
the executor builds for it. XLA:CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec import programs
from presto_tpu.expr.ir import col
from presto_tpu.ops import build_join, join, probe_expand
from presto_tpu.page import Page
from presto_tpu.runner import QueryRunner
from presto_tpu.types import BIGINT

DOMAIN = [(1, 9)]
# build: duplicate keys, one NULL key, one dead row; payload = 100 + row
BUILD_KEYS = np.array([5, 2, 5, 7, 2, 5, 9, 4, 7, 3], dtype=np.int64)
BUILD_KEY_VALID = np.array([True] * 7 + [False] + [True] * 2)
BUILD_LIVE = np.array([True] * 9 + [False])

# probe keys by layout: 1, 6 and 8 match nothing; 4 is NULL in the build,
# 3 is a dead build row
_HIT = [5, 2, 7, 9, 5]
LAYOUTS = {
    "no_zero": _HIT,
    "zero_head": [1, 6] + _HIT,
    "zero_middle": _HIT[:2] + [8, 3, 4] + _HIT[2:],
    "zero_tail": _HIT + [6, 8, 1],
    "zero_head_middle_tail": [1] + _HIT[:3] + [6, 6] + _HIT[3:] + [8, 4],
    "all_dead": _HIT,
}


def _page(columns, valids, live):
    page = Page.from_arrays(columns, [BIGINT] * len(columns), valids=valids)
    return Page(page.blocks, jnp.asarray(live))


def _pages(layout):
    keys = np.array(LAYOUTS[layout], dtype=np.int64)
    n = len(keys)
    live = np.ones(n, dtype=bool)
    key_valid = np.ones(n, dtype=bool)
    if layout == "all_dead":
        live[:] = False
    elif layout == "zero_head_middle_tail":
        live[2] = False  # a dead row among the live ones emits nothing
        key_valid[3] = False  # nor does a NULL key, but for a left join
    build = _page(
        [BUILD_KEYS, 100 + np.arange(len(BUILD_KEYS), dtype=np.int64)],
        [BUILD_KEY_VALID, None], BUILD_LIVE)
    probe = _page([keys, -np.arange(n, dtype=np.int64)],
                  [key_valid, None], live)
    return build, probe, keys, live & key_valid, live


def _reference(keys, key_ok, live, kind):
    """(probe row, build row or -1) of every output slot, in slot order:
    the probe rows repeated by their counts, each over its matches in
    build-row order (the build's sort is stable)."""
    matches = []
    for k, ok in zip(keys, key_ok):
        hit = (BUILD_KEYS == k) & BUILD_KEY_VALID & BUILD_LIVE & ok
        matches.append(np.flatnonzero(hit))
    counts = np.array([len(m) for m in matches])
    if kind == "left":
        counts = np.where(live & (counts == 0), 1, counts)
    p_row = np.repeat(np.arange(len(keys)), counts)
    j = np.arange(len(p_row)) - (np.cumsum(counts) - counts)[p_row]
    b_row = np.array([matches[p][i] if len(matches[p]) else -1
                      for p, i in zip(p_row, j)], dtype=np.int64)
    return p_row, b_row


@pytest.fixture(params=["sorted", "starts"])
def leg(request):
    """Both range lookups in front of the expansion: the binary search
    over the sorted keys, and the chip's CSR table."""
    if request.param == "starts":
        join.set_direct_join_override(True)
        yield DOMAIN
        join.set_direct_join_override(None)
    else:
        yield None


@pytest.mark.parametrize("return_matched", [False, True])
@pytest.mark.parametrize("room", ["equal", "above", "below"])
@pytest.mark.parametrize("kind", ["inner", "left"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_live_slots_equal_numpy_repeat(leg, layout, kind, room,
                                       return_matched):
    build, probe, keys, key_ok, live = _pages(layout)
    p_row, b_row = _reference(keys, key_ok, live, kind)
    total = len(p_row)
    cap = max({"equal": total, "above": total + 5,
               "below": total // 2}[room], 1)
    jb = build_join(build, [col(0, BIGINT)], key_domains=leg)
    assert (jb.starts is not None) == (leg is not None)
    res = probe_expand(jb, probe, [col(0, BIGINT)], out_capacity=cap,
                       key_domains=leg, kind=kind,
                       return_matched=return_matched)
    assert len(res) == (3 if return_matched else 2)
    out, got_total = res[0], res[1]
    # the true total, also where the page is truncated
    assert int(got_total) == total
    n = min(total, cap)
    mask = np.asarray(out.row_mask)
    assert mask[:n].all() and not mask[n:].any()
    for b in out.blocks:
        assert not np.asarray(b.valid)[n:].any()
    p_key, p_tag, b_key, b_tag = out.blocks
    hit = b_row[:n] >= 0
    assert (np.asarray(p_tag.data)[:n] == -p_row[:n]).all()
    assert np.asarray(p_tag.valid)[:n].all()
    assert (np.asarray(p_key.data)[:n] == keys[p_row[:n]]).all()
    # null-extended where nothing matched, else the match in build order
    assert (np.asarray(b_tag.valid)[:n] == hit).all()
    assert (np.asarray(b_key.valid)[:n] == hit).all()
    assert (np.asarray(b_tag.data)[:n][hit] == 100 + b_row[:n][hit]).all()
    if return_matched:
        want = np.zeros(len(BUILD_KEYS), dtype=bool)
        want[b_row[:n][hit]] = True
        assert (np.asarray(res[2]) == want).all()


@pytest.mark.parametrize("room", ["above", "below"])
@pytest.mark.parametrize("kind", ["inner", "left"])
def test_wide_counts_over_many_rows(kind, room):
    """A page of a few thousand rows with counts from 0 to 40, so the
    running maximum crosses long runs and long gaps."""
    rng = np.random.default_rng(28)
    n_keys = 500
    per_key = rng.integers(0, 41, size=n_keys)
    per_key[[0, 1, 250, n_keys - 1]] = 0
    b_keys = np.repeat(np.arange(1, n_keys + 1), per_key).astype(np.int64)
    rng.shuffle(b_keys)
    p_keys = rng.integers(1, n_keys + 1, size=3000).astype(np.int64)
    live = rng.random(3000) < 0.8
    build = Page.from_arrays([b_keys], [BIGINT])
    probe = _page([p_keys, np.arange(3000, dtype=np.int64)], None, live)
    counts = np.where(live, per_key[p_keys - 1], 0)
    if kind == "left":
        counts = np.where(live & (counts == 0), 1, counts)
    ref = np.repeat(np.arange(3000), counts)
    cap = len(ref) + 100 if room == "above" else len(ref) // 3
    out, total = probe_expand(
        build_join(build, [col(0, BIGINT)]), probe, [col(0, BIGINT)],
        out_capacity=cap, kind=kind, build_output=[])
    assert int(total) == len(ref)
    n = min(cap, len(ref))
    assert int(np.asarray(out.row_mask).sum()) == n
    assert (np.asarray(out.blocks[1].data)[:n] == ref[:n]).all()


def test_expanding_probe_program_has_no_control_flow(monkeypatch):
    """The program ``_expanding_join_pages`` registers, under the leg the
    chip takes: no ``while`` (the slot search was one of 18 rounds) and
    no conditional in its lowered text."""
    calls = []
    real = programs.Program.__call__

    def spy(self, *args, **kwargs):
        if self.kind == "join_probe":
            calls.append((self, args, kwargs))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(programs.Program, "__call__", spy)
    join.set_direct_join_override(True)
    try:
        catalog = Catalog()
        catalog.register("tpch", Tpch(sf=0.01, split_rows=16384,
                                      orderless_third=True))
        runner = QueryRunner(catalog, programs=programs.ProgramRegistry())
        n = runner.execute(
            "select count(*), count(o_orderkey) from customer "
            "left outer join orders on c_custkey = o_custkey").rows
    finally:
        join.set_direct_join_override(None)
    assert n == [(15500, 15000)]  # 500 orderless customers, null-extended
    assert calls, "the query never ran an expanding probe"
    for prog, args, kwargs in calls:
        assert kwargs["out_capacity"] >= 1024
        lowered = prog.fn.lower(*args, **kwargs)
        assert "join:expand" in lowered.as_text(debug_info=True)
        text = lowered.as_text()
        for op in ("while", "stablehlo.case", "stablehlo.if"):
            assert op not in text, op
        assert "scatter" in text and "cummax" in text
