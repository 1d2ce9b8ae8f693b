"""The ``tpch_fkjoin`` generator: the program's ``connectors/tpch.py``
with ``O_CUSTKEY`` drawn as the spec draws it (clause 4.2.3): never a
customer key divisible by 3, so a third of the customers hold no
orders.  That third is Q13's largest bucket and every null-extended
row its left join emits; ``generators/tpch.py`` keeps the connector's
older uniform draw, which the stored answers of ``tpch_sf1`` and
``tpch_sf10`` were made from.  A name of its own, so ``tables.py``
keeps this population's host columns apart from theirs.

A program whose connector lacks the argument cannot serve this
configuration: the call raises ``TypeError`` at load, before anything
is measured.
"""


def connector(config: dict):
    from presto_tpu.connectors.tpch import Tpch

    return Tpch(sf=float(config["scale_factor"]),
                split_rows=int(config["split_rows"]),
                orderless_third=True)
