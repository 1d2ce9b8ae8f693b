"""Executor: per traced pass, the summed ``host_read:<why>`` spans of
its queries (``exec/local.host_read``: the host waiting for a device
value in mid-query), ``host_read:result`` left out because the
``device_get`` span has it; median over the traced passes.  A program
without such spans reports nothing."""

from benchmark import stats

NAME = "host_read_wait_ms"
UNIT = "ms"
SPAN = "host_read:"


def read(run):
    per_pass = [[(name, end - start) for q in p.queries
                 for name, start, end in q.spans if name.startswith(SPAN)]
                for p in run.traced]
    if not any(per_pass):
        return None
    return stats.median([
        sum(s for name, s in spans if name != SPAN + "result") * 1e3
        for spans in per_pass])
