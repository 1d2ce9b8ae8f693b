"""Parse / bind / plan (``sql/``, ``planner/``, ``analysis/``): the
server's ``planningMs`` summed per pass; median over the window's
passes.  Every statement's text is new, so the plan cache never
answers."""

from benchmark import stats

NAME = "planning_ms"
UNIT = "ms"


def read(run):
    return stats.median([
        sum(q.stats.get("planningMs", 0.0) for q in p.queries)
        for p in run.passes])
