"""Mesh tier (``parallel/dist.py``, ``parallel/exchange.py``): per
traced pass, the summed device durations of chip 0's collective
operations; median over the traced passes.  Cells on several chips."""

from benchmark import stats, xplane

NAME = "collective_ms"
UNIT = "ms"


def read(run):
    if run.cell.chips < 2 or run.trace is None or not run.trace.chips:
        return None
    chip = min(run.trace.chips)
    chip0, in_flight = run.trace.chips[chip], run.trace.async_ops.get(chip, [])
    return stats.median([xplane.collective_s(chip0, in_flight, *span) * 1e3
                         for span in run.pass_intervals])
