"""Executor: the server's ``expandRetries`` (statement stats: expanding
probes that ``exec/local._probe_with_retry`` ran a second time because
the first capacity was too small) summed per pass; median over the
window's passes.  A program without the counter reports nothing."""

from benchmark import stats

NAME = "expand_retries_per_pass"
UNIT = "count"
WORKLOADS = ["tpch_sf1_fkjoin.csr_join"]


def read(run):
    if not any("expandRetries" in q.stats
               for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("expandRetries", 0) for q in p.queries)
        for p in run.passes])
