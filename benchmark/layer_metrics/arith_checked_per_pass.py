"""Kernels: the server's ``arithChecked`` (statement stats: the
guarded arithmetic sites and the limb sums of short addends that the
statement's chains compiled WITH their runtime guard, the wrap mask
of a multiply, add, subtract or negation, the zero check of a division,
a sum's row-by-row limb split, because the plan's intervals could not
prove it away: ``exec/chain.Chain.arith_counts``, counted when
``exec/local._chain_pages`` lowers a chain) summed per pass; median
over the window's passes.  0 where every site was proven
(``arithProven`` beside it counts those).  A sum counts as proven for
the pages its proof covers (a capacity the stage signs, 2^26 rows or
more in these cells, whose pages hold 2^23: docs/observability.md); a
larger page would split row by row and still count so.  A program
without the counter reports nothing."""

from benchmark import stats

NAME = "arith_checked_per_pass"
UNIT = "count"


def read(run):
    if not any("arithChecked" in q.stats
               for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("arithChecked", 0) for q in p.queries)
        for p in run.passes])
