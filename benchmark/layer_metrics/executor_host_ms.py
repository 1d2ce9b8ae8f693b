"""Executor (``exec/local.py``, ``exec/tasks.py``): per traced pass,
the server's ``executionMs`` less the time chip 0 was busy inside the
pass; median over the traced passes.  What the host spends between
device operations: dispatch per split, host reads, result
materialisation."""

from benchmark import stats

NAME = "executor_host_ms"
UNIT = "ms"


def read(run):
    passes = [p for p in run.traced if run.pass_interval(p)]
    return stats.median([
        sum(q.stats.get("executionMs", 0.0) for q in p.queries) - busy_s * 1e3
        for p, busy_s in zip(passes, run.busy_s_per_pass())])
