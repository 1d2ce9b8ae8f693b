"""Kernels: the least time one chip's HBM could take to read what a
pass must read (``bytes_needed.py``) over the time chip 0 was busy in
the pass.  One-chip cells: on a mesh the bytes are spread over the
chips.  The peak comes from ``peaks.json`` by device kind; a run on a
device without one (the CPU rehearsal) reports nothing."""

from benchmark import bytes_needed, stats

NAME = "hbm_roofline_pct"
UNIT = "%"


def read(run):
    if run.cell.chips != 1 or run.peaks is None or run.trace is None \
            or run.trace.stands_in:
        return None
    busy_s = stats.median(run.busy_s_per_pass())
    if not busy_s:
        return None
    need = bytes_needed.pass_bytes(run.cell.config, run.cell.queries,
                                   run.row_counts)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy_s
