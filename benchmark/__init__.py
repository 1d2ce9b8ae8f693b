"""The benchmark of presto_tpu: served TPC-H on the chip.

The yardstick lives here, where a PR that claims a gain cannot change
it: traffic generation, the reduction from traces and spans to metrics,
the table of peaks, the bytes-needed function, the plain reference and
the comparison that decides ``correct``.  From the program it takes
only the system under test (``presto_tpu.*``) and its spans, counters
and kernel names.  ``run.py`` is the command; everything that belongs
to one configuration, one traffic mix, one cell or one per-layer metric
is a file of its own that the harness finds by name (PERF.md, §3-4).
"""
