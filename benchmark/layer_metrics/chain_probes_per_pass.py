"""Executor: the server's ``chainProbes`` (statement stats: the probes
the statement's streaming chains run in a row over each of their
pages, ``exec/chain.Chain.probes`` counted when
``exec/local._chain_pages`` lowers a chain) summed per pass; median
over the window's passes.  6 in ``tpcds_sf10.star_join``: two in
``ds_q03``'s fact chain and four in ``ds_q07``'s.  A program without
the counter reports nothing."""

from benchmark import stats

NAME = "chain_probes_per_pass"
UNIT = "count"
WORKLOADS = ["tpcds_sf10.star_join"]


def read(run):
    if not any("chainProbes" in q.stats
               for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("chainProbes", 0) for q in p.queries)
        for p in run.passes])
