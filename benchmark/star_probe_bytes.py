"""The bytes the first probes of a star's fact chains must move: for
each query of a pass, the fact table's rows times the stored bytes of
the key its first probe looks up, plus the first dimension's rows times
its key's bytes (the build reads that column once).  Which columns
those are comes from the query's sidecar (``queries/<dir>/<query>.json``
``star``: ``fact`` and ``first_build``, table -> columns), bytes per
value from the configuration's ``tables``, rows from the load.  Nothing
here looks at the program's buffers, capacities or page count, so the
count is the same whatever implements the lookup, and with every key of
the star 8 bytes wide a plan that orders its probes otherwise still
reads 8 bytes a fact row: the numerator of ``star_probe_roofline_pct``.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmark import bytes_needed, specs


@functools.lru_cache(maxsize=None)
def _star(query_dir: str, query: str, root: str):
    return specs.read_json(root, "queries", query_dir,
                           query + ".json").get("star")


def query_bytes(config: dict, query: str, row_counts: Dict[str, int],
                root: str = specs.ROOT) -> int:
    """0 for a query whose sidecar names no star."""
    star = _star(config["queries"], query, root)
    if not star:
        return 0
    return sum(bytes_needed.query_bytes(config, star[side], row_counts)
               for side in ("fact", "first_build"))


def pass_bytes(config: dict, queries, row_counts: Dict[str, int],
               root: str = specs.ROOT) -> int:
    """``queries``: the pass's, anything with a ``name``."""
    return sum(query_bytes(config, q.name, row_counts, root)
               for q in queries)
