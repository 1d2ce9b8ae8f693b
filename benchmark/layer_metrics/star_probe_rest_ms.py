"""Kernels: per traced pass, the time chip 0 ran operations under the
scopes ``probe:1``, ``probe:2``, ... at any depth: every probe of a
streaming chain behind its first (``exec/chain.py``), which in a star's
fact chain run over the rows the first probe left; median over the
traced passes.  Every pass of this cell has such probes, so no time
under the scopes means a program from before them: it reports nothing,
not 0."""

from benchmark import scopes

NAME = "star_probe_rest_ms"
UNIT = "ms"
WORKLOADS = ["tpcds_sf10.star_join"]

#: more probes in a row than any chain has
REST = tuple(f"probe:{i}" for i in range(1, 64))


def read(run):
    return scopes.ms_per_pass(run, *REST, depth=None) or None
