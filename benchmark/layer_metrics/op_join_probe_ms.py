"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``op:Join``: the probe stage of a chain and the expanding probe
(``exec/local.py``), key lookup (``join:lookup``) and the gathers of
the build's columns; median over the traced passes.  Cells with a
join."""

from benchmark import scopes

NAME = "op_join_probe_ms"
UNIT = "ms"
WORKLOADS = ["tpch_sf10.join", "tpch_sf1.join_agg",
             "tpch_sf1_fkjoin.csr_join"]


def read(run):
    return scopes.ms_per_pass(run, "op:Join")
