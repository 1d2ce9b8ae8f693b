"""The ``tpch`` generator: the program's own ``connectors/tpch.py``.

TPC-H's population is fixed by the spec; the connector hashes
(table, column, index) and takes no seed, so the data is the same in
every run (the configuration files say so).  A configuration names its
generator; a later one (``tpcds``, a skewed ``tpch``) is a new file
here with the same function.
"""


def connector(config: dict):
    from presto_tpu.connectors.tpch import Tpch

    return Tpch(sf=float(config["scale_factor"]),
                split_rows=int(config["split_rows"]))
