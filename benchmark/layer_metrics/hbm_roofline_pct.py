"""Kernels: the least time the cell's chips' HBM could take to read
what a pass must read (``bytes_needed.py``) over the time the busiest
chip was busy in the pass: ``need / (chips x peak) / busy``, median
over the traced passes.  On one chip that is chip 0's busy time.  On a
mesh the bytes a pass must read are spread over the cell's chips, so
the number is the share of the mesh's summed bandwidth that the pass's
least bytes would fill while its busiest chip works; some chip reads at
least ``need / chips``, so it stays under 100% however the mesh splits
them.  Every cell in which the reader finds what it needs, and no list
of cells anywhere: a peak for the device kind in ``peaks.json`` (the
CPU rehearsal has none) and a device plane in the trace; otherwise
nothing, never 0."""

from benchmark import bytes_needed, stats

NAME = "hbm_roofline_pct"
UNIT = "%"


def read(run):
    if run.peaks is None or run.trace is None or run.trace.stands_in:
        return None
    busy_s = stats.median(run.busiest_s_per_pass())
    if not busy_s:
        return None
    need = bytes_needed.pass_bytes(run.cell.config, run.cell.queries,
                                   run.row_counts)
    mesh_bytes_per_s = run.cell.chips * run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / mesh_bytes_per_s / busy_s
