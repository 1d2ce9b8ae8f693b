"""Executor: per traced pass, the ``presto:dispatch:*`` events of the
trace's host plane (one per program call: the host's side of it, from
the call to the return of its asynchronous dispatch, and a compile
where one happens), in ms (``benchmark/host_spans.py``); median over
the traced passes.  Every cell: nothing for a program without the span
(before PR 39)."""

from benchmark import host_spans

NAME = "dispatch_ms"
UNIT = "ms"


def read(run):
    return host_spans.ms_per_pass(
        run, lambda e: e.name.startswith("dispatch:"))
