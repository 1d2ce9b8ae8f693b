"""Kernels: the least time one chip's HBM could take to move what a
pass's expanding probes must move (``expand_bytes.py``: from the
server's ``expandedRows`` and the configuration's bytes per value,
never from the program's buffers) over the time chip 0 ran operations
under ``join:expand`` in the pass.  Bound by bytes: an expansion does
no arithmetic to speak of.  One-chip cells with a peak only, like
``hbm_roofline_pct``; a program without the counter or the scope
reports nothing."""

from benchmark import expand_bytes, scopes, stats

NAME = "join_expand_roofline_pct"
UNIT = "%"
WORKLOADS = ["tpch_sf1_fkjoin.csr_join"]


def read(run):
    if run.cell.chips != 1 or run.peaks is None or run.trace is None \
            or run.trace.stands_in:
        return None
    need = stats.median([
        expand_bytes.pass_bytes(run.cell.config, p.queries, run.row_counts)
        for p in run.passes
        if any("expandedRows" in q.stats for q in p.queries)])
    expand_ms = scopes.ms_per_pass(run, "join:expand", depth=None)
    if not need or not expand_ms:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (expand_ms / 1e3)
