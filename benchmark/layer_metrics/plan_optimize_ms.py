"""Parse / bind / plan: per traced pass, the ``presto:plan:optimize``
events of the trace's host plane (``IterativeOptimizer.optimize``), in
ms (``benchmark/host_spans.py``); median over the traced passes.
Every cell: nothing for a program without the span (before PR 39)."""

from benchmark import host_spans

NAME = "plan_optimize_ms"
UNIT = "ms"


def read(run):
    return host_spans.ms_per_pass(run, lambda e: e.name == "plan:optimize")
