"""The ``tpcds`` generator: the program's own ``connectors/tpcds.py``,
returned as it is (``tables.load`` asks a generator for no more than
``MemoryConnector.load_from`` asks of a connector), with the dimension
sizes the configuration names set on it.

The connector keeps SF1's ``item`` (18,000 rows) and ``promotion``
(300) at every scale factor; the spec's table 3-2 steps them (102,000
and 500 at SF10).  Both are attributes the connector reads when it
generates a split, draws a foreign key or builds a dictionary, so a
configuration's ``dimension_rows`` sets them here, before anything is
generated, and a program from before this file can serve the
configuration too.  A configuration without the key gets the
connector's own sizes.

``customer_demographics`` is the spec's 1,920,800-row cross product at
every scale factor.  Beside SF0.01's 28,800 fact rows it is the larger
table, and the planner then probes ``date_dim`` first and compacts
nothing: not the plan SF10 gets.  ``cd_rows`` cuts it (the connector's
own argument for test harnesses); only a test's copy of a configuration
sets it, no committed file does.

Like TPC-H's, the population hashes (table, column, index) and takes
no seed: ``--seed`` orders the traffic.
"""

#: ``dimension_rows`` key -> the connector's attribute
SIZES = {"item": "n_items", "promotion": "n_promos"}


def connector(config: dict):
    from presto_tpu.connectors.tpcds import Tpcds

    conn = Tpcds(sf=float(config["scale_factor"]),
                 split_rows=int(config["split_rows"]),
                 cd_rows=config.get("cd_rows"))
    for table, rows in config.get("dimension_rows", {}).items():
        setattr(conn, SIZES[table], int(rows))
    return conn
