"""Client + protocol (``client.py``, ``server/coordinator.py``): per
pass, the client's latency less the server's ``planningMs`` and
``executionMs``; median over the window's passes.  HTTP, JSON, row
encoding and decoding, admission and polling."""

from benchmark import stats

NAME = "protocol_ms"
UNIT = "ms"


def read(run):
    return stats.median([
        sum(q.client_ms - q.stats.get("planningMs", 0.0)
            - q.stats.get("executionMs", 0.0) for q in p.queries)
        for p in run.passes])
