"""Program cache (``exec/programs.py``): the warm-up's first call of
each query of the cell, summed.  Cold it is the compile; from a warm
persistent cache it is tracing, lowering and loading."""

NAME = "first_call_s"
UNIT = "s"


def read(run):
    return run.phases.get("first_call_s")
