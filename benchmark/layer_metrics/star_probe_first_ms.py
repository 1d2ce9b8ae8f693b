"""Kernels: per traced pass, the time chip 0 ran operations under the
scope ``probe:0`` at any depth: the first probe of each streaming
chain, counted from the leaf (``exec/chain.py`` opens ``probe:<i>``
inside ``op:Join``; where the chain compacts inside its first probe
the lookup and the fetch both carry ``probe:0`` and the compaction
between them stays ``op:Filter/filter:compact``).  In a star's fact
chain that is the probe every fact row takes; median over the traced
passes.  With ``star_probe_rest_ms`` it adds up to
``op_join_probe_ms`` where every probe runs in a chain.  Every pass of
this cell probes, so no time under the scope means a program from
before the scope: it reports nothing, not 0."""

from benchmark import scopes

NAME = "star_probe_first_ms"
UNIT = "ms"
WORKLOADS = ["tpcds_sf10.star_join"]


def read(run):
    return scopes.ms_per_pass(run, "probe:0", depth=None) or None
