"""Kernels: the least time one chip's HBM could take to move what the
first probes of a pass must move (``star_probe_bytes.py``: the fact
table's rows times its key's stored bytes plus the first dimension's
key column, from the sidecars, the configuration and the load's row
counts, never from the program's buffers) over the time chip 0 ran
operations under ``probe:0`` in the pass (``star_probe_first_ms``).
Bound by bytes: a key lookup does no arithmetic to speak of.  One-chip
cells with a peak and a device plane only, like
``join_expand_roofline_pct``; a program without the scope reports
nothing."""

from benchmark import scopes, star_probe_bytes

NAME = "star_probe_roofline_pct"
UNIT = "%"
WORKLOADS = ["tpcds_sf10.star_join"]


def read(run):
    if run.cell.chips != 1 or run.peaks is None or run.trace is None \
            or run.trace.stands_in:
        return None
    need = star_probe_bytes.pass_bytes(run.cell.config, run.cell.queries,
                                       run.row_counts)
    first_ms = scopes.ms_per_pass(run, "probe:0", depth=None)
    if not need or not first_ms:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (first_ms / 1e3)
