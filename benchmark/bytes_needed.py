"""The bytes a query must read: for every column it touches, the rows
of its table times the stored bytes per value.  Columns come from the
query's sidecar (``queries/<dir>/<query>.json`` ``reads``), bytes per
value from the configuration's ``tables``, rows from the load.  Validity
bytes and masks are not counted: this is the least the algorithm needs,
the denominator of ``hbm_roofline_pct``."""

from __future__ import annotations

from typing import Dict, List


def query_bytes(config: dict, reads: Dict[str, List[str]],
                row_counts: Dict[str, int]) -> int:
    return sum(row_counts[table] * config["tables"][table][column]
               for table, columns in reads.items() for column in columns)


def pass_bytes(config: dict, queries, row_counts: Dict[str, int]) -> int:
    return sum(query_bytes(config, q.reads, row_counts) for q in queries)
