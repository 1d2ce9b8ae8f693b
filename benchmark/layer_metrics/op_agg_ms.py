"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``op:Aggregation``: a chain's partial aggregation, the fold
tower, the packed and running folds and the finals
(``exec/local.py``); median over the traced passes.  What fuses into
the reduce (checked products, CASE) is read here."""

from benchmark import scopes

NAME = "op_agg_ms"
UNIT = "ms"


def read(run):
    return scopes.ms_per_pass(run, "op:Aggregation")
