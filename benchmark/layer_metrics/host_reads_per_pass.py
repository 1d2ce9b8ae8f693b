"""Executor: the server's ``hostReads`` (statement stats: blocking
device reads the query made, ``exec/local.host_read``) summed per
pass; median over the window's passes.  A program without the counter
reports nothing."""

from benchmark import stats

NAME = "host_reads_per_pass"
UNIT = "count"


def read(run):
    if not any("hostReads" in q.stats for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("hostReads", 0) for q in p.queries)
        for p in run.passes])
