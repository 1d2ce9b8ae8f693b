"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``join:index`` at any depth (``ops/join.build_join``'s sorted
leg: the ``argsort`` of the packed keys, ``key[order]`` and the CSR
``starts`` table over the key domain); median over the traced passes.
The cell whose builds are not primary keys; a build that leaves by the
unique-direct leg never opens the scope.  A program without the scope
(before PR 27) reads 0."""

from benchmark import scopes

NAME = "join_index_ms"
UNIT = "ms"
WORKLOADS = ["tpch_sf1_fkjoin.csr_join"]


def read(run):
    return scopes.ms_per_pass(run, "join:index", depth=None)
