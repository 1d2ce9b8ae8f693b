"""Tables: device bytes in use after the load, summed over the chips.
Nothing where the backend keeps no memory statistics (XLA:CPU)."""

NAME = "table_gb"
UNIT = "GB"


def read(run):
    b = run.phases.get("table_bytes")
    return b / 1e9 if b else None
