"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``agg:sort`` at any depth (``ops/aggregate._sorted_group_ids``,
``_presorted_group_ids``: the argsort of the packed keys and the
group-id scatter); median over the traced passes.  The cells with a
sort-based aggregation."""

from benchmark import scopes

NAME = "agg_sort_ms"
UNIT = "ms"
WORKLOADS = ["tpch_sf1.join_agg", "tpch_sf1_fkjoin.csr_join"]


def read(run):
    return scopes.ms_per_pass(run, "agg:sort", depth=None)
