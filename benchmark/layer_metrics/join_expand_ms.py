"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``join:expand`` at any depth (``ops/join.probe_expand`` after its
range lookup: the offsets, the scatter and the two ``cummax`` that map
output slots to probe rows (PR 28; no control flow) and the gathers of
both sides at the output capacity, the wasted first try of a probe
that ran again included); median over the traced passes.  A program
without the scope (before PR 27) reads 0."""

from benchmark import scopes

NAME = "join_expand_ms"
UNIT = "ms"
WORKLOADS = ["tpch_sf1_fkjoin.csr_join"]


def read(run):
    return scopes.ms_per_pass(run, "join:expand", depth=None)
