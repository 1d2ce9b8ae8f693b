"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``agg:sort`` at any depth (``ops/aggregate._sorted_runs``: the
one sort of the key with the batch's lanes, and the run boundaries;
``_presorted_group_ids``: the run boundaries of input that arrives
grouped); median over the traced passes.  Every cell; 0 where no
operation of a pass ran under the scope (every aggregation of the
pass took the masked-reduce path, or there is none), nothing where the
trace has no scopes at all."""

from benchmark import scopes

NAME = "agg_sort_ms"
UNIT = "ms"


def read(run):
    return scopes.ms_per_pass(run, "agg:sort", depth=None)
