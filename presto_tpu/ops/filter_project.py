"""Filter and projection over Pages.

Reference analog: FilterAndProjectOperator
(operator/FilterAndProjectOperator.java:31) + the JIT'd PageProcessor
(operator/project/PageProcessor.java:77-102). The reference evaluates a
compiled PageFilter into SelectedPositions then materializes projections
position-by-position; here the filter just ANDs into the row mask and
projections are whole-column jnp computations that XLA fuses.  The
mask is free and shapes stay static; what consumes the page is not
free, because it still runs over every dead slot.  So a consumer that
pays per slot (a probe's gathers) can ask for ``compact_page`` first:
the live rows, in order, in a page of a smaller static capacity.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.expr.compile import (
    ExprCompiler, compile_filter, proven_sites,
)
from presto_tpu.expr.ir import Expr
from presto_tpu.page import Block, Page


def filter_page(page: Page, predicate: Expr,
                proven: Optional[Sequence[bool]] = None) -> Page:
    """Rows where predicate is not TRUE (false or NULL) are masked out.
    ``proven``: one outcome per guarded arithmetic site of the
    predicate (``analysis.ranges.arith_sites`` order; True = the plan's
    intervals proved the guard away), None = every guard stays."""
    return Page(page.blocks, compile_filter(
        predicate, page, proven=proven_sites([predicate], proven))(page))


def project_page(page: Page, projections: Sequence[Expr],
                 proven: Optional[Sequence[bool]] = None) -> Page:
    """Produce a new Page with one block per projection expression.

    Dictionary provenance: a projection that is a bare ColumnRef keeps
    the source block's dictionary (dictionary-aware projection,
    DictionaryAwarePageProjection.java analog).  ``proven`` as
    ``filter_page``'s, over all projections in order.
    """
    from presto_tpu.expr.compile import expr_dictionary

    c = ExprCompiler.for_page(
        page, proven=proven_sites(list(projections), proven))
    dicts = [b.dictionary for b in page.blocks]
    blocks: List[Block] = []
    for e in projections:
        data, valid = c.compile(e)(page)
        wants_dict = e.type.is_string or (
            e.type.is_array and e.type.element is not None
            and e.type.element.is_string)
        dictionary = expr_dictionary(e, dicts) if wants_dict else None
        if data.dtype != e.type.np_dtype:
            data = data.astype(e.type.np_dtype)
        blocks.append(Block(data, valid, e.type, dictionary))
    return Page(tuple(blocks), page.row_mask)


def compact_page(page: Page, cap_out: int) -> Tuple[Page, jax.Array]:
    """(the first ``cap_out`` live rows of ``page`` in their order, as
    a page of capacity ``cap_out`` with the live rows first; the live
    count of ``page``).  All of the live rows only when the count is
    at most ``cap_out``: the caller compares, and works on ``page``
    itself otherwise.  Types and dictionaries are kept; a dead slot's
    ``valid`` is False.

    A stable sort of the dead flag carries the row numbers of the
    live rows to the front, and each column is then gathered at the
    first ``cap_out`` of them: a sort over every slot, but gathers over
    ``cap_out`` only.  (On a v5e that sort costs a quarter of a binary
    search of the mask's running count and a third of a scatter of the
    row numbers: PERF.md, PR 26.)"""
    cap = page.capacity
    with jax.named_scope("filter:compact"):
        # the sort's key is a flag, 0 or 1 in any width
        dead = jnp.logical_not(  # lint: allow(narrow-cast)
            page.row_mask).astype(jnp.int8)
        _, rows = jax.lax.sort((dead, jnp.arange(cap, dtype=jnp.int32)),
                               num_keys=1, is_stable=True)
        rows = rows[:cap_out]
        live = page.num_rows()
        mask = jnp.arange(cap_out, dtype=jnp.int32) < live
        blocks = tuple(
            Block(b.data[rows], b.valid[rows] & mask, b.type, b.dictionary)
            for b in page.blocks)
        return Page(blocks, mask), live
