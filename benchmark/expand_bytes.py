"""The bytes an expanding join must move: every column of every row it
emits written once and read once from its side, and both key columns
read once.  The emitted rows are the server's count
(``stats.expandedRows``: the ``total`` each expanding probe read); the
columns come from the query's sidecar
(``queries/<dir>/<query>.json`` ``expands``: ``emits`` and ``keys``,
table -> columns), bytes per value from the configuration's ``tables``,
key rows from the load.  Nothing here looks at the program's buffers,
capacities or retries, so the count is the same whatever implements the
expansion: the numerator of ``join_expand_roofline_pct``."""

from __future__ import annotations

import functools
from typing import Dict

from benchmark import bytes_needed, specs


@functools.lru_cache(maxsize=None)
def _expands(query_dir: str, query: str, root: str):
    return specs.read_json(root, "queries", query_dir,
                           query + ".json").get("expands")


def query_bytes(config: dict, query: str, expanded_rows: int,
                row_counts: Dict[str, int], root: str = specs.ROOT) -> int:
    """0 for a query whose sidecar names no expansion."""
    expands = _expands(config["queries"], query, root)
    if not expands:
        return 0
    row_bytes = sum(config["tables"][table][column]
                    for table, columns in expands["emits"].items()
                    for column in columns)
    return (2 * expanded_rows * row_bytes
            + bytes_needed.query_bytes(config, expands["keys"], row_counts))


def pass_bytes(config: dict, records, row_counts: Dict[str, int],
               root: str = specs.ROOT) -> int:
    """``records``: the pass's statements (``loadgen.QueryRecord``: a
    ``name`` and the final page's ``stats``)."""
    return sum(query_bytes(config, q.name, q.stats.get("expandedRows", 0),
                           row_counts, root) for q in records)
