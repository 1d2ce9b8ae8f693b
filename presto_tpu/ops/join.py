"""Hash-join kernels: build + probe via sorted lookup.

Reference analog: HashBuilderOperator (operator/HashBuilderOperator.java:51)
building PagesIndex/PagesHash (operator/PagesHash.java:34 — open
addressing over build rows with synthetic addresses) probed by
LookupJoinOperator (operator/LookupJoinOperator.java:53) through
JoinProbe. Random-probe hash tables serialize on TPU, so the build side
is instead *sorted by join key* and probes are vectorized
``searchsorted`` binary searches — every probe row resolves its match
range [lo, hi) in parallel on the VPU.

Match semantics: keys are packed exactly (domains from table metadata;
TPC-H keys always fit 63 bits) so equality is exact, or hash-mixed as a
fallback. NULL join keys never match (SQL semantics) — they pack to the
reserved 0 code which is excluded, or sort to the +inf sentinel.

Shapes: probe_join aligned outputs (unique build keys, or first-match)
keep the probe page's capacity. probe_expand emits up to out_capacity
rows for many-to-many joins and returns the true match count beside
them; the driver checks it and probes again at a capacity that fits
(the analog of the reference's yielding LookupJoinPageBuilder). The map
from output slot to probe row there is one scatter and one running
maximum, not a search: the slots are an ``arange`` and the rows'
offsets a prefix sum, so each emitting row marks its first slot and the
scan carries the mark over the rest (a binary search did the same in
18 gather rounds, 380 times slower on a v5e at Q13's size; PERF.md,
PR 28)."""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.expr.compile import ExprCompiler
from presto_tpu.expr.ir import Expr
from presto_tpu.ops.aggregate import pack_or_hash_keys
from presto_tpu.page import Block, Page

_I64_MAX = jnp.iinfo(jnp.int64).max

# Direct-address lookup table cap: when the packed-key domain is dense
# enough, the build also materializes a table over the FULL key domain
# so every probe resolves its match without ~log2(build) serialized
# binary-search rounds (the TPU answer to PagesHash.java:152's O(1)
# open-addressing probe).  Two tables, one per leg: the sorted leg's
# CSR ``starts`` offsets (two int32 gathers give a key's [lo, hi)), and
# a unique build's ``rank`` (one int32 gather gives the key's sorted
# position, or -1 where it is absent).  Bounded in absolute size (HBM)
# and relative to the build (so a tiny build over a huge sparse domain
# doesn't pay a domain-sized sort).
DIRECT_DOMAIN_MAX = 1 << 26
DIRECT_DOMAIN_PER_ROW = 64


# Direct-table selection resolves ONCE per process (first runner
# construction warms it) instead of re-reading the environment inside
# every build_join call — the per-build hot path.  The explicit
# override hook exists for tests, which flip legs in-process (tier-1
# runs on XLA:CPU and must still run the leg the chip selects).
_DIRECT_JOIN_RESOLVED: "Optional[bool]" = None


def set_direct_join_override(value: "Optional[bool]") -> None:
    """Force the direct-address join table on/off (None re-resolves
    from the environment/backend on next use)."""
    global _DIRECT_JOIN_RESOLVED
    _DIRECT_JOIN_RESOLVED = None if value is None else bool(value)


def resolve_direct_join() -> bool:
    """The direct table pays a domain-sized fused sort at build time to
    make probes O(1) gathers.  That trade wins on TPU (binary-search
    probes serialize ~log2(build) gather rounds; measured CPU-vs-TPU in
    PERF.md) but LOSES on XLA:CPU, whose searchsorted is already cheap
    and whose domain-sized sort is not (TPC-H Q3 SF1 measured 1.7x
    slower with the table).  Env override PRESTO_TPU_DIRECT_JOIN=0/1
    forces it off/on for A/B runs; resolved once per process."""
    global _DIRECT_JOIN_RESOLVED
    if _DIRECT_JOIN_RESOLVED is None:
        import os as _os

        force = _os.environ.get("PRESTO_TPU_DIRECT_JOIN")
        if force is not None:
            _DIRECT_JOIN_RESOLVED = force not in ("0", "false", "")
        else:
            import jax as _jax

            _DIRECT_JOIN_RESOLVED = _jax.default_backend() != "cpu"
    return _DIRECT_JOIN_RESOLVED


def _direct_table_profitable() -> bool:
    return resolve_direct_join()


def _direct_budget(page: Page) -> int:
    """Largest key domain worth a direct-address table for this build
    size (shared by the sorted and unique paths so they agree)."""
    return min(DIRECT_DOMAIN_MAX,
               max(1 << 20, DIRECT_DOMAIN_PER_ROW * page.capacity))


def packed_domain_size(domains) -> Optional[int]:
    """Size of the packed-key code space [0, prod) when every key
    column has a known domain (mirrors pack_or_hash_keys' exact path:
    per-column cardinality hi-lo+2 with code 0 reserved for NULL)."""
    if not domains or any(d is None for d in domains):
        return None
    prod = 1
    for lo, hi in domains:
        prod *= int(hi - lo + 2)
    return prod if prod < (1 << 62) else None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JoinBuild:
    """Sorted build-side index (LookupSource analog)."""

    sorted_keys: jax.Array  # packed keys (cap,), max-sentinel padded
    perm: jax.Array  # int32 (cap,): sorted pos -> build row
    page: Page  # original build page (payload source)
    # sorted leg's optional direct-address table: starts[k] = first
    # sorted position with key >= k, for k in [0, domain_size]; int32
    # (domain_size+1,)
    starts: Optional[jax.Array] = None
    # sort-free unique-build path: False iff the planner's uniqueness
    # promise was violated at runtime (caller rebuilds via the sort)
    unique_ok: Optional[jax.Array] = None
    # three-valued IN/NOT IN support (HashSemiJoinOperator.java:32):
    # whether any live build row had a NULL key, and whether the build
    # had any live row at all — device bool scalars
    has_null_key: Optional[jax.Array] = None
    nonempty: Optional[jax.Array] = None
    # unique-direct leg's table: rank[k] = the sorted position of key k,
    # -1 where no live build row has it; int32 (domain_size,)
    rank: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.sorted_keys, self.perm, self.page, self.starts,
                self.unique_ok, self.has_null_key, self.nonempty,
                self.rank), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.sorted_keys.shape[0]


def build_join(
    page: Page,
    key_exprs: Sequence[Expr],
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    null_safe: bool = False,
    unique: bool = False,
) -> JoinBuild:
    """``null_safe``: NULL keys match each other (IS NOT DISTINCT FROM
    — the INTERSECT/EXCEPT comparison; default SQL joins drop them).
    ``unique``: the planner promises distinct build keys (primary-key
    joins) — with a dense exact domain the build then skips the sort
    entirely: ranks come from a prefix count over the domain, and the
    direct-address table holds each key's rank, -1 where it is absent
    (PagesHash's addressing rebuilt as two scatters + one scan, probed
    by one gather a row; a violated promise
    is detected and reported through ``unique_ok`` for the caller to
    rebuild via the sort path)."""
    c = ExprCompiler.for_page(page)
    kd = [c.compile(e)(page) for e in key_exprs]
    from presto_tpu.ops.aggregate import canonicalize_codes, expr_key_dicts

    datas = canonicalize_codes([d for d, _ in kd],
                               expr_key_dicts(page, key_exprs))
    valids = [v for _, v in kd]
    key, exact = pack_or_hash_keys(datas, valids, key_domains)
    live = page.row_mask
    if not null_safe:
        # NULL keys never participate: exclude rows with any null key
        for v in valids:
            live = live & v
    key = jnp.where(live, key, jnp.iinfo(key.dtype).max)

    # three-valued IN/NOT IN metadata (cheap reductions; only the
    # null-aware semi/anti/mark probes read them)
    nonempty = jnp.any(page.row_mask)
    all_valid = valids[0]
    for v in valids[1:]:
        all_valid = all_valid & v
    has_null = jnp.any(page.row_mask & jnp.logical_not(all_valid))

    prod_u = (packed_domain_size(key_domains)
              if unique and exact else None)
    if prod_u is not None and prod_u <= _direct_budget(page):
        cap = page.capacity
        key_c = jnp.clip(key, 0, prod_u - 1)
        slot = jnp.where(live, key_c, prod_u)
        counts = jnp.zeros(prod_u + 1, jnp.int32).at[slot].add(
            jnp.where(live, 1, 0))
        present = jnp.minimum(counts[:prod_u], 1)
        table = jnp.where(present > 0,
                          jnp.cumsum(present).astype(jnp.int32) - 1, -1)
        # a live row's key is present, so its entry is its rank
        rank = table[key_c.astype(jnp.int32)]
        tgt = jnp.where(live, rank.astype(jnp.int64), cap)
        sorted_keys = jnp.full((cap,), jnp.iinfo(key.dtype).max,
                               dtype=key.dtype).at[tgt].set(key, mode="drop")
        order_u = jnp.zeros((cap,), jnp.int32).at[tgt].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
        collision = jnp.any(counts[:prod_u] > 1)
        return JoinBuild(sorted_keys, order_u, page,
                         unique_ok=jnp.logical_not(collision),
                         has_null_key=has_null, nonempty=nonempty,
                         rank=table)

    with jax.named_scope("join:index"):
        order = jnp.argsort(key)
        sorted_keys = key[order]

        starts = None
        prod = (packed_domain_size(key_domains)
                if exact and _direct_table_profitable() else None)
        if prod is not None and prod <= _direct_budget(page):
            # one fused sort at build time buys O(1)-gather probes
            # forever: dead/sentinel rows sort past prod-1 so they
            # never enter a range
            queries = jnp.arange(prod + 1, dtype=sorted_keys.dtype)
            starts = jnp.searchsorted(
                sorted_keys, queries, method="sort").astype(jnp.int32)
    return JoinBuild(sorted_keys, order.astype(jnp.int32), page, starts,
                     has_null_key=has_null, nonempty=nonempty)


def build_null_flags(page: Page, key_exprs: Sequence[Expr]):
    """(has_null_key, nonempty) of a build-side page WITHOUT building
    the sorted index — used by partitioned joins to compute the GLOBAL
    three-valued-IN flags across partitions (a build NULL in one
    partition makes every unmatched probe everywhere UNKNOWN)."""
    c = ExprCompiler.for_page(page)
    valids = [c.compile(e)(page)[1] for e in key_exprs]
    all_valid = valids[0]
    for v in valids[1:]:
        all_valid = all_valid & v
    return (jnp.any(page.row_mask & jnp.logical_not(all_valid)),
            jnp.any(page.row_mask))


def _rank_of(build: JoinBuild, key: jax.Array):
    """A unique-direct build's one gather: (sorted position, found) per
    probe row, position 0 where the key is absent or off the domain."""
    d = build.rank.shape[0]
    r = build.rank[jnp.clip(key, 0, d - 1).astype(jnp.int32)]
    found = (r >= 0) & (key >= 0) & (key < d)
    return jnp.where(found, r, 0), found


@jax.named_scope("join:lookup")
def _lookup_first(build: JoinBuild, key: jax.Array):
    """(candidate sorted position, key-match mask) per probe row."""
    if build.rank is not None:
        return _rank_of(build, key)
    if build.starts is not None:
        d = build.starts.shape[0] - 1
        kk = jnp.clip(key, 0, d - 1)
        lo = build.starts[kk]
        hi = build.starts[kk + 1]
        in_dom = (key >= 0) & (key < d)
        return jnp.clip(lo, 0, build.capacity - 1), (hi > lo) & in_dom
    pos = jnp.searchsorted(build.sorted_keys, key)
    pos_c = jnp.clip(pos, 0, build.capacity - 1)
    return pos_c, build.sorted_keys[pos_c] == key


@jax.named_scope("join:lookup")
def _lookup_range(build: JoinBuild, key: jax.Array):
    """[lo, hi) sorted-position match range per probe row."""
    if build.rank is not None:
        lo, found = _rank_of(build, key)
        return lo, lo + found.astype(lo.dtype)
    if build.starts is not None:
        d = build.starts.shape[0] - 1
        kk = jnp.clip(key, 0, d - 1)
        lo = build.starts[kk]
        hi = build.starts[kk + 1]
        in_dom = (key >= 0) & (key < d)
        zero = jnp.zeros((), dtype=lo.dtype)
        return jnp.where(in_dom, lo, zero), jnp.where(in_dom, hi, zero)
    lo = jnp.searchsorted(build.sorted_keys, key, side="left")
    hi = jnp.searchsorted(build.sorted_keys, key, side="right")
    return lo, hi


def _probe_keys(page: Page, key_exprs: Sequence[Expr], key_domains,
                null_safe: bool = False):
    c = ExprCompiler.for_page(page)
    kd = [c.compile(e)(page) for e in key_exprs]
    from presto_tpu.ops.aggregate import canonicalize_codes, expr_key_dicts

    datas = canonicalize_codes([d for d, _ in kd],
                               expr_key_dicts(page, key_exprs))
    valids = [v for _, v in kd]
    key, _ = pack_or_hash_keys(datas, valids, key_domains)
    ok = page.row_mask
    if not null_safe:
        for v in valids:
            ok = ok & v
    # distinct sentinel from the build's (max): never matches build keys
    return jnp.where(ok, key, jnp.iinfo(key.dtype).max - 1), ok


def probe_lookup(
    build: JoinBuild,
    probe: Page,
    probe_key_exprs: Sequence[Expr],
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    null_safe: bool = False,
):
    """The first half of ``probe_join``: per probe row the candidate
    sorted position in ``build``, whether a live probe row found its
    key there, and whether the row's key took part (no NULL in it, or
    ``null_safe``).  Nothing of the build's rows is read yet, so a
    chain may move the matched rows to a smaller page between this and
    ``probe_fetch`` (``exec/chain.Lookup``)."""
    key, ok = _probe_keys(probe, probe_key_exprs, key_domains, null_safe)
    pos_c, found = _lookup_first(build, key)
    return pos_c, found & probe.row_mask, ok


def probe_fetch(
    build: JoinBuild,
    probe: Page,
    pos_c: jax.Array,
    match: jax.Array,
    kind: str = "inner",
    build_output: Optional[Sequence[int]] = None,
) -> Page:
    """The second half of ``probe_join`` for the kinds that emit build
    columns (inner | left): the build row behind each candidate
    position, and the selected build blocks gathered there."""
    build_row = build.perm[pos_c]
    if build_output is None:
        build_output = range(len(build.page.blocks))
    out_blocks: List[Block] = list(probe.blocks)
    for i in build_output:
        b = build.page.blocks[i]
        data = b.data[build_row]
        valid = b.valid[build_row] & match
        out_blocks.append(Block(data, valid, b.type, b.dictionary))
    if kind == "inner":
        mask = probe.row_mask & match
    elif kind == "left":
        mask = probe.row_mask
    else:
        raise ValueError(kind)
    return Page(tuple(out_blocks), mask)


def probe_join(
    build: JoinBuild,
    probe: Page,
    probe_key_exprs: Sequence[Expr],
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    kind: str = "inner",
    build_output: Optional[Sequence[int]] = None,
    null_safe: bool = False,
    null_aware: bool = False,
) -> Page:
    """Probe-aligned join for unique (or first-match) build keys:
    ``probe_lookup``, then the kind's use of it.

    kind: inner | left | semi | anti | mark.
    Output: probe blocks followed by the selected build blocks
    (build_output indexes into build.page.blocks; default all).
    semi/anti emit probe blocks only, with the row mask filtered.

    ``null_aware`` selects ANSI three-valued IN/NOT IN semantics
    (HashSemiJoinOperator.java:32): an unmatched probe whose key is
    NULL — or any unmatched probe when the build holds a NULL key —
    is UNKNOWN, which filters as FALSE (semi/anti) and surfaces as a
    NULL mark.  IN over an empty subquery stays FALSE for every probe,
    NULL keys included.
    """
    pos_c, match, ok = probe_lookup(build, probe, probe_key_exprs,
                                    key_domains, null_safe)

    if null_aware and kind in ("semi", "anti", "mark") \
            and build.has_null_key is not None:
        has_null = build.has_null_key
        nonempty = build.nonempty
        # UNKNOWN rows: unmatched with a NULL somewhere in the
        # comparison (probe key NULL against a nonempty build, or any
        # build-side NULL key); empty build is decidedly FALSE
        unknown = jnp.logical_not(match) & nonempty & (
            jnp.logical_not(ok) | has_null)
        if kind == "semi":
            return Page(probe.blocks, probe.row_mask & match)
        if kind == "anti":
            keep = jnp.logical_not(match) & jnp.logical_not(unknown)
            return Page(probe.blocks, probe.row_mask & keep)
        from presto_tpu.types import BOOLEAN

        mark = Block(match, jnp.logical_not(unknown), BOOLEAN)
        return Page(tuple(probe.blocks) + (mark,), probe.row_mask)

    if kind == "semi":
        return Page(probe.blocks, probe.row_mask & match)
    if kind == "anti":
        return Page(probe.blocks, probe.row_mask & jnp.logical_not(match))
    if kind == "mark":
        # mark join: emit the presence test as a BOOLEAN column instead
        # of filtering — EXISTS/IN under OR (the reference's mark
        # semijoin, MarkDistinct/SemiJoinRewriter role)
        from presto_tpu.types import BOOLEAN

        mark = Block(match, jnp.ones_like(probe.row_mask), BOOLEAN)
        return Page(tuple(probe.blocks) + (mark,), probe.row_mask)

    return probe_fetch(build, probe, pos_c, match, kind, build_output)


def probe_expand(
    build: JoinBuild,
    probe: Page,
    probe_key_exprs: Sequence[Expr],
    out_capacity: int,
    key_domains: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    kind: str = "inner",
    build_output: Optional[Sequence[int]] = None,
    return_matched: bool = False,
    null_safe: bool = False,
) -> Tuple[Page, jax.Array]:
    """Many-to-many join: each probe row emits one output row per
    matching build row. Returns (page, total_matches); if
    total_matches > out_capacity the page is truncated (its first
    out_capacity rows) and the driver probes again at a capacity that
    holds total_matches. Slots at or past total_matches are dead:
    ``row_mask`` and every ``valid`` false, the data unspecified.

    No control flow: output slot -> probe row is a scatter of the
    emitting rows' numbers to their first slots and a ``cummax`` over
    the slots, in int32.

    kind: inner | left (left emits one null-extended row for probes
    with no match).

    return_matched: additionally return a bool (build_capacity,) mask of
    build rows touched by a match — the driver ORs these across probe
    pages to emit the FULL OUTER tail (reference:
    operator/LookupOuterOperator.java, which streams unvisited build
    positions after all probes finish)."""
    key, _ = _probe_keys(probe, probe_key_exprs, key_domains, null_safe)
    lo, hi = _lookup_range(build, key)
    with jax.named_scope("join:expand"):
        counts = jnp.where(probe.row_mask, hi - lo, 0)
        if kind == "left":
            counts = jnp.where(probe.row_mask & (counts == 0), 1, counts)
        offsets = jnp.cumsum(counts) - counts
        total = jnp.sum(counts)

        # probe row of each output slot, in one pass: a row that emits
        # anything owns the slots from its offset on, and those offsets
        # are distinct, so each such row writes its number (+1: 0 says
        # "no row starts here") into its first slot and a running
        # maximum carries it over the rest. Rows that emit nothing, and
        # rows past a truncated page's end, aim past the end and drop.
        rows = jnp.arange(probe.capacity, dtype=jnp.int32)
        out_idx = jnp.arange(out_capacity, dtype=jnp.int32)
        first = jnp.where(counts > 0, offsets, out_capacity + rows)
        marks = jnp.zeros((out_capacity,), jnp.int32).at[first].set(
            rows + 1, mode="drop", unique_indices=True)
        p_row = jnp.maximum(jax.lax.cummax(marks) - 1, 0)
        # ... and the slot the row starts at, by the same scan
        j = out_idx - jax.lax.cummax(jnp.where(marks > 0, out_idx, 0))
        live_out = out_idx < total
        b_pos = jnp.clip(lo[p_row] + j, 0, build.capacity - 1)
        # false only for left-join null rows
        matched = j < (hi[p_row] - lo[p_row])
        b_row = build.perm[b_pos]

        out_blocks: List[Block] = []
        for b in probe.blocks:
            out_blocks.append(
                Block(b.data[p_row], b.valid[p_row] & live_out, b.type,
                      b.dictionary)
            )
        if build_output is None:
            build_output = range(len(build.page.blocks))
        for i in build_output:
            b = build.page.blocks[i]
            out_blocks.append(
                Block(b.data[b_row], b.valid[b_row] & matched & live_out,
                      b.type, b.dictionary)
            )
        out_page = Page(tuple(out_blocks), live_out)
        if return_matched:
            b_matched = jnp.zeros((build.page.capacity,), dtype=jnp.bool_)
            b_matched = b_matched.at[b_row].max(matched & live_out, mode="drop")
            return out_page, total, b_matched
        return out_page, total


def outer_build_tail(
    build: JoinBuild,
    matched: jax.Array,
    probe_types_dicts: Sequence[Tuple],
    build_output: Optional[Sequence[int]] = None,
) -> Page:
    """FULL OUTER tail: build rows never matched by any probe page,
    null-extended on the probe columns. ``probe_types_dicts`` is
    [(Type, Dictionary|None)] for the probe side's output layout."""
    cap = build.page.capacity
    blocks: List[Block] = []
    for t, d in probe_types_dicts:
        blocks.append(
            Block(jnp.zeros((cap,) + t.value_shape, dtype=t.np_dtype),
                  jnp.zeros(cap, dtype=jnp.bool_), t, d)
        )
    if build_output is None:
        build_output = range(len(build.page.blocks))
    for i in build_output:
        blocks.append(build.page.blocks[i])
    return Page(tuple(blocks), build.page.row_mask & jnp.logical_not(matched))
