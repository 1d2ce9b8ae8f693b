"""Kernels: per traced pass, the share of chip 0's busy time in
operations under no ``op:`` scope (programs outside the executor's
registry such as ``_pad_arrays``, plan-time row counts, operations XLA
gave no ``op_name``); median over the traced passes.  What the
per-operator metrics cannot book."""

from benchmark import scopes, stats

NAME = "device_unscoped_pct"
UNIT = "%"


def read(run):
    ops = scopes.for_run(run)
    if ops is None:
        return None
    bare = [o for o in ops if not o.scopes]
    return stats.median([100.0 * scopes.busy_s(bare, *span) / busy
                         for span in run.pass_intervals
                         if (busy := scopes.busy_s(ops, *span))])
