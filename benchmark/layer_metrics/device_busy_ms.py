"""Kernels (``ops/``, ``expr/compile.py``): per traced pass, the union
of chip 0's operation intervals inside the pass; median over the traced
passes."""

from benchmark import stats

NAME = "device_busy_ms"
UNIT = "ms"


def read(run):
    return stats.median([s * 1e3 for s in run.busy_s_per_pass()])
