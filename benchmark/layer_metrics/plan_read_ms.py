"""Parse / bind / plan: per traced pass, the ``presto:host_read:*``
events inside a ``presto:plan`` event of the trace's host plane, in
ms (``benchmark/host_spans.py``: the bind-time row counts, on the
device's clock); median over the traced passes.  Every cell: 0 where
the plans read nothing, nothing for a program without the ``plan:*``
spans (before PR 39)."""

from benchmark import host_spans

NAME = "plan_read_ms"
UNIT = "ms"


def read(run):
    events = host_spans.for_run(run)
    if not events or not any(e.name.startswith("plan:") for e in events):
        return None
    plans = [e for e in events if e.name == "plan"]

    def in_plan(e):
        return e.name.startswith("host_read:") and any(
            p.start <= e.start and e.end <= p.end for p in plans)

    return host_spans.ms_per_pass(run, in_plan) or 0.0
