"""Executor: the server's ``compactFallbackPages`` (statement stats:
pages of compacting chains that held more live rows than their small
page, so that the aggregation over the chain threw them away and ran
again over the program that does not compact,
``exec/local._chain_pages``) summed per pass; median over the window's
passes.  The mechanism's miss rate: 0 where every estimate held.  A
program without the counter reports nothing."""

from benchmark import stats

NAME = "compact_fallback_pages_per_pass"
UNIT = "count"


def read(run):
    if not any("compactFallbackPages" in q.stats
               for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("compactFallbackPages", 0) for q in p.queries)
        for p in run.passes])
