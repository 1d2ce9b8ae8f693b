"""Tables (``connectors/memory.py``, ``connectors/tpch.py``): the load
phase on the host clock, generation or cache mapping plus upload."""

NAME = "load_s"
UNIT = "s"


def read(run):
    return run.phases.get("load_s")
