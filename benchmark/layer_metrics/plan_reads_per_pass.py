"""Parse / bind / plan: the server's ``planReads`` (statement stats:
blocking device reads the statement made while it planned, through
``obs.reads.host_read``: the memory connector's bind-time row counts,
one a page of every table a statement resolves) summed per pass;
median over the window's passes.  Every cell: a program from before
the counter reports nothing."""

from benchmark import stats

NAME = "plan_reads_per_pass"
UNIT = "count"


def read(run):
    if not any("planReads" in q.stats for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("planReads", 0) for q in p.queries)
        for p in run.passes])
