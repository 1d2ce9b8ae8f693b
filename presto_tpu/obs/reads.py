"""Blocking device reads: the one helper every read that makes the host
wait for the device goes through, in the executor (``exec/``) and at
bind time (``connectors/memory.MemoryConnector.row_count``).

Each read is a ``host_read:<why>`` span when the query traces (what a
device-idle gap is booked to), one of ``device.get_calls`` /
``device.get_bytes`` and one of this thread's ``host_reads``.  The
runner takes the count's difference across ``plan`` (the statement's
``planReads``) and across ``execute`` (``hostReads``).  Not routed
here: the ``collect_stats`` row count, the range sanitizer,
``parallel/`` (docs/observability.md).
"""

from __future__ import annotations

import threading

import jax

from presto_tpu.obs.metrics import METRICS
from presto_tpu.obs.trace import span

# this thread's blocking reads, never reset (``host_reads``)
_READS = threading.local()


def host_reads() -> int:
    """Blocking host reads this thread has made so far.  A statement's
    counts are differences across its phases: the reads are made on
    the query's own thread (like ``_task_stats``), never on a scheduler
    worker, and nothing is kept on the shared runner."""
    return getattr(_READS, "n", 0)


def host_read(x, why: str):
    """``x``, a device array or a pytree of them, as NumPy on the
    host."""
    with span("host_read:" + why, cat="device"):
        out = jax.device_get(x)
    _READS.n = getattr(_READS, "n", 0) + 1
    METRICS.counter("device.get_calls").inc()
    METRICS.counter("device.get_bytes").inc(sum(
        getattr(a, "nbytes", 0) for a in jax.tree_util.tree_leaves(out)))
    return out
