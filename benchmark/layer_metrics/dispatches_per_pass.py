"""Executor: the server's ``dispatches`` (statement stats: programs the
statement called while it executed, counted where each is called,
``exec/programs.Program.__call__`` and the helpers
``exec/programs.dispatched`` wraps) summed per pass; median over the
window's passes.  Every cell: a program from before the counter
reports nothing."""

from benchmark import stats

NAME = "dispatches_per_pass"
UNIT = "count"


def read(run):
    if not any("dispatches" in q.stats for p in run.passes for q in p.queries):
        return None
    return stats.median([
        sum(q.stats.get("dispatches", 0) for q in p.queries)
        for p in run.passes])
