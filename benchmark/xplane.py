"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.  Read with ``jax.profiler.ProfileData``, which
needs nothing but JAX.  Checked against a small recorded trace in
``tests/benchmark_tests/test_trace_reduction.py``.

What counts as the device: the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, one event per executed HLO operation, named
by its HLO text; the ``XLA Modules`` line gives the program
(``jit_<fn>``) each ran in.  Busy time is the union of those intervals;
idle share is 1 minus busy over the stretch.  ``Async XLA Ops`` holds
what is in flight beside them (copies, asynchronous collectives) and
does not count as busy.  A CPU rehearsal has no device plane; there the host
events that carry an ``hlo_op`` stat stand in as the chip their
``device_ordinal`` stat names (chip 0 where it names none), so that the
reduction runs end to end in the tests, on virtual devices as on chips
(such a run says ``"platform": "cpu"`` and its numbers are never device
numbers).

The benchmark marks each statement of the traced stretch with a
``jax.profiler.TraceAnnotation`` named ``bench:<query>:<seq>`` on the
client thread; those host events are on the trace's clock, so they
bound each query and each pass there, and tie the program's own
``perf_counter`` spans to it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # copies and collectives in flight
MARK = "bench:"
#: HLO operations that move data between chips
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast", "ragged-all-to-all")

Interval = Tuple[float, float]  # start, end, in seconds on the trace's clock


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str
    module: str


@dataclasses.dataclass
class Trace:
    chips: Dict[int, List[Op]]  # chip -> its operations, by start
    async_ops: Dict[int, List[Op]]  # chip -> operations in flight beside them
    marks: Dict[str, Interval]  # "bench:<query>:<seq>" -> interval
    stands_in: bool  # host events stand in for the device (rehearsal)


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(hlo: str) -> str:
    """An operation's name from the text XLA gives its event on the
    device: ``%fusion.12 = (...) fusion(...)`` is ``fusion.12``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def _module_name(event_name: str) -> str:
    """``jit_chain(1234567)`` is ``jit_chain``."""
    return event_name.split("(", 1)[0]


def read(path: str) -> Trace:
    """The trace at ``path``, an ``.xplane.pb`` or its ``.gz``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    chips: Dict[int, List[Op]] = {}
    async_ops: Dict[int, List[Op]] = {}
    marks: Dict[str, Interval] = {}
    host_ops: Dict[int, List[Op]] = {}  # device ordinal -> stand-ins
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            tail = plane.name[len(DEVICE_PLANE):].split()[0]
            if not tail.isdigit():
                continue
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9,
                 _module_name(e.name))
                for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in modules]

            def module_at(t: float) -> str:
                i = bisect.bisect_right(starts, t) - 1
                return modules[i][2] if i >= 0 and t < modules[i][1] else ""

            for name, into in ((OPS_LINE, chips), (ASYNC_LINE, async_ops)):
                if name not in lines:
                    continue
                ops = into.setdefault(int(tail), [])
                for e in lines[name].events:
                    start = e.start_ns / 1e9
                    ops.append(Op(start, start + e.duration_ns / 1e9,
                                  short_name(e.name), module_at(start)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK):
                        marks[e.name] = (e.start_ns / 1e9,
                                         (e.start_ns + e.duration_ns) / 1e9)
                    elif not chips:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            ordinal = int(stats.get("device_ordinal", 0))
                            host_ops.setdefault(ordinal, []).append(Op(
                                e.start_ns / 1e9,
                                (e.start_ns + e.duration_ns) / 1e9, e.name,
                                str(stats.get("hlo_module", ""))))
    stands_in = not chips and bool(host_ops)
    if stands_in:
        chips.update(host_ops)
    for ops in list(chips.values()) + list(async_ops.values()):
        ops.sort(key=lambda o: o.start)
    return Trace(chips, async_ops, marks, stands_in)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """The intervals merged where they touch or overlap, by start."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if e > lo and s < hi]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] no interval covers."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def busy(ops: Sequence[Op]) -> List[Interval]:
    return union([(o.start, o.end) for o in ops])


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def collective_s(ops: Sequence[Op], in_flight: Sequence[Op], lo: float,
                 hi: float) -> float:
    """Summed durations of the collective operations that start in
    [lo, hi): the synchronous ones among ``ops``, and the asynchronous
    ones from start to done among ``in_flight`` (their ``-start`` and
    ``-done`` halves among ``ops`` are then not counted again)."""
    def half(name: str) -> bool:
        head = name.split(".", 1)[0]
        return head.endswith("-start") or head.endswith("-done")

    return (sum(o.end - o.start for o in ops if lo <= o.start < hi
                and is_collective(o.name) and not half(o.name))
            + sum(o.end - o.start for o in in_flight if lo <= o.start < hi
                  and is_collective(o.name)))


def op_totals(ops: Sequence[Op], lo: float, hi: float,
              top: int = 10) -> List[Tuple[str, float]]:
    """Seconds per operation, ``<module>/<op>`` under the names XLA
    gives them, for operations that start in [lo, hi); the ``top``
    largest."""
    totals: Dict[str, float] = {}
    for o in ops:
        if lo <= o.start < hi:
            key = f"{o.module}/{o.name}" if o.module else o.name
            totals[key] = totals.get(key, 0.0) + (o.end - o.start)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def innermost(spans: Sequence[Tuple[str, float, float]],
              t: float) -> Optional[str]:
    """The name of the span open at ``t`` that started last; spans are
    (name, start, end) on one clock."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def attribute_gaps(idle: Sequence[Interval],
                   queries: Sequence[Tuple[str, float, float]],
                   spans: Sequence[Tuple[str, float, float]],
                   top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap goes, whole,
    to ``<query in flight>/<innermost program span open>`` at its
    middle.  ``queries`` and ``spans`` are (name, start, end) on the
    trace's clock.  A gap with no query in flight is
    ``client/between_queries``; one inside a query but outside every
    program span is ``<query>/protocol``."""
    totals: Dict[str, float] = {}
    for s, e in idle:
        mid = (s + e) / 2.0
        query = innermost(queries, mid)
        if query is None:
            key = "client/between_queries"
        else:
            key = f"{query}/{innermost(spans, mid) or 'protocol'}"
        totals[key] = totals.get(key, 0.0) + (e - s)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]
