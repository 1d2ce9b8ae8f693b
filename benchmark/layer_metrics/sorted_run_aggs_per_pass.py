"""Kernels: the server's ``sortedRunAggs`` (statement stats: the
aggregations that took the sort path, the chains' partials and the
fold tower's merges, each program call counted once, from what its
trace counted: ``exec/programs.sorted_runs``) summed per pass; median
over the window's passes.  0 where no aggregation sorts (a global
aggregation, a streaming one, or keys few enough to be their own
slots); ``sortedRunSorts`` beside it counts the sorts those ran, more
than one where the aggregates' inputs ride the key's sort in batches.
A program without the counter reports nothing."""

from benchmark import stats

NAME = "sorted_run_aggs_per_pass"
UNIT = "count"


def read(run):
    if not any("sortedRunAggs" in q.stats
               for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("sortedRunAggs", 0) for q in p.queries)
        for p in run.passes])
