"""What the host was doing, on the device's clock: the program's own
spans as the profile holds them.

Every live span of a traced statement (``presto_tpu/obs/trace.py``) is
also a ``jax.profiler.TraceAnnotation`` named ``presto:<span>``: an
event of its thread's line in the trace's host plane, on the clock the
device's operations are on.  This reads those events from the run's
``.xplane.pb`` (``trace_out/<cell>``, where ``run.py`` writes it), once
a process, with ``jax.profiler.ProfileData``, for the readers of host
spans under ``layer_metrics/``: no shift of the program's
``perf_counter`` spans by a statement's mark.

The spans and who opens them (docs/observability.md): ``query``,
``parse``, ``plan`` (``plan:bind``, ``plan:check``, ``plan:optimize``,
``plan:estimates``), ``execute``, ``statement:admit``,
``statement:finish``, ``statement:respond``; in the executor ``op:*``
per page pull, ``chain:lower``, ``dispatch:<kind>`` per program call,
``host_read:<why>`` per blocking read, ``device_get``.  A program from
before a span has no such event; its readers then give None.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import stats, xplane

PREFIX = "presto:"
#: where run.py writes a cell's trace: trace_out/<cell>
TRACE_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "trace_out")
#: spans that say what the host was doing, not only which phase it
#: was in: a gap booked to one of these is named (``idle_named_pct``);
#: a name ending in ":" is a prefix
LEAVES = ("parse", "plan:", "host_read:", "dispatch:", "chain:lower",
          "statement:", "device_get")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str  # the span's name, without the prefix
    start: float  # seconds on the trace's clock
    end: float


def is_leaf(name: Optional[str]) -> bool:
    return name is not None and any(
        name.startswith(leaf) if leaf.endswith(":") else name == leaf
        for leaf in LEAVES)


def read(path: str) -> List[Event]:
    """The ``presto:*`` events of every host plane at ``path``, an
    ``.xplane.pb`` or its ``.gz``, by start."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = [Event(e.name[len(PREFIX):], e.start_ns / 1e9,
                 (e.start_ns + e.duration_ns) / 1e9)
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda e: e.start)


@functools.lru_cache(maxsize=4)
def _read_cached(path: str) -> List[Event]:
    return read(path)


def for_run(run) -> Optional[List[Event]]:
    """The host spans of ``run``'s trace, found by the rule of
    ``run.py`` and read once per process; None where the run has no
    traced pass or no trace file."""
    if run.trace is None or not run.pass_intervals:
        return None
    path = xplane.find_xplane(os.path.join(TRACE_OUT, run.cell.name))
    if path is None:
        return None
    return _read_cached(path)


def ms_per_pass(run, keep) -> Optional[float]:
    """The summed durations, in ms, of the events that start inside a
    traced pass and that ``keep(event)`` keeps; median over the passes.
    None where the run has no host spans or ``keep`` keeps none in any
    pass (a program from before the span)."""
    events = for_run(run)
    if not events:
        return None
    per_pass = [[e for e in events if lo <= e.start < hi and keep(e)]
                for lo, hi in run.pass_intervals]
    if not any(per_pass):
        return None
    return stats.median([sum(e.end - e.start for e in kept) * 1e3
                         for kept in per_pass])


def innermost(events: Sequence[Event], t: float) -> Optional[str]:
    """The name of the event open at ``t`` that started last (the rule
    of ``xplane.attribute_gaps``)."""
    return xplane.innermost([(e.name, e.start, e.end) for e in events], t)


def statements(run) -> List[Tuple[float, float]]:
    """The traced statements' intervals on the trace's clock, from the
    benchmark's marks."""
    marks = (run.trace.marks.get(f"{xplane.MARK}{q.name}:{q.seq}")
             for p in run.traced for q in p.queries)
    return [m for m in marks if m is not None]


def idle_booking(run) -> Optional[Dict[Optional[str], float]]:
    """Chip 0's idle seconds inside the traced statements, each gap
    booked, whole, to the innermost host span open at its middle (None:
    no span); None where the run has no chip, no host spans or no idle
    time."""
    events = for_run(run)
    if not events or not run.busy:
        return None
    merged = run.busy[min(run.busy)]
    out: Dict[Optional[str], float] = {}
    for lo, hi in statements(run):
        inside = [e for e in events if e.start < hi and e.end > lo]
        for s, e in xplane.gaps(merged, lo, hi):
            name = innermost(inside, (s + e) / 2.0)
            out[name] = out.get(name, 0.0) + (e - s)
    return out if sum(out.values()) > 0 else None
