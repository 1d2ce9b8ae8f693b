"""Device: the share of chip 0's idle time inside the traced statements
that is booked to a span saying what the host was doing (a leaf:
``parse``, ``plan:*``, ``host_read:*``, ``dispatch:*``,
``chain:lower``, ``statement:*``, ``device_get``) rather than to a
phase (``query``, ``plan``, ``execute``, an ``op:*`` page pull) or to
no span at all.  Each gap is booked, whole, to the innermost
``presto:*`` event of the trace's host plane open at its middle
(``benchmark/host_spans.py``, the rule of ``xplane.attribute_gaps``).
Every cell: a program from before PR 39 reports its ``host_read:*``
and ``device_get`` share; nothing where the run has no chip or no idle
time."""

from benchmark import host_spans

NAME = "idle_named_pct"
UNIT = "%"


def read(run):
    booked = host_spans.idle_booking(run)
    if booked is None:
        return None
    named = sum(s for name, s in booked.items() if host_spans.is_leaf(name))
    return 100.0 * named / sum(booked.values())
