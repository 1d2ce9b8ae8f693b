"""Kernels: per traced pass, the time chip 0 ran operations under the
scopes ``op:Filter`` and ``op:Project`` (opened per chain stage by the
stage's ``apply`` in ``exec/chain.py``; a chain's compaction is
``op:Filter/filter:compact``); median over the traced passes.  Near zero where XLA fuses the
predicate and the projections into their consumer: a fusion is booked
to the scope on its own metadata (``benchmark/scopes.py``)."""

from benchmark import scopes

NAME = "op_filter_project_ms"
UNIT = "ms"


def read(run):
    return scopes.ms_per_pass(run, "op:Filter", "op:Project")
