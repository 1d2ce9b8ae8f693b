"""Which operator the chip's time belongs to: chip 0's operations of a
traced run with the program and the ``jax.named_scope`` path each ran
under, for the per-operator metrics under ``layer_metrics/``.

Where the names are (settled on a v5e trace, PR 24): an ``XLA Ops``
event of a ``/device:TPU:<n>`` plane carries only its timing;
``jax.profiler.ProfileData`` shows no more.  The event's *metadata*
(``XPlane.event_metadata``, which ``ProfileData`` does not expose)
carries the stats ``tf_op``, the operation's ``op_name`` as
``jit(<function>)/<scope>/.../<primitive>:``, and ``program_id``, the
number in the name of the program's ``XLA Modules`` event
(``jit_<function>(<program_id>)``).  So the ``.xplane.pb`` is read
here as protobuf wire format: fields by number from
``tsl/profiler/protobuf/xplane.proto``, no generated module
(``xplane_pb2`` imports only with the whole of tensorflow).

A fused operation is booked to the scope on the fusion's own
metadata: the resolution of this measurement.  The scopes are opened
in ``presto_tpu/exec/local.py`` (``op:<Node>`` per chain stage and per
program) and ``presto_tpu/ops/`` (``agg:sort``, ``agg:reduce``,
``join:lookup``).  A trace without them gives ``None``, never an error:
a CPU rehearsal (no device plane), a trace trimmed of its stats, a
program from before PR 24 (no ``op:`` scope anywhere).
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark import xplane

#: the first level of scope, one per plan operator
OPERATOR = "op:"
#: where run.py writes a cell's trace: trace_out/<cell>
TRACE_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "trace_out")


@dataclasses.dataclass
class ScopedOp:
    start: float  # seconds on the trace's clock, as xplane.Op
    end: float
    module: str  # "jit_chain_leaf_filter_agg_k0a1"
    scopes: Tuple[str, ...]  # ("op:Aggregation", "agg:reduce"); () if none


# -- protobuf wire format ---------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5 (maps: key = 1, value = 2); XLine: name = 2,
# timestamp_ns = 3, events = 4; XEvent: metadata_id = 1, offset_ps = 2,
# duration_ps = 3; XEventMetadata: id = 1, name = 2, stats = 5;
# XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# uint64_value = 3, int64_value = 4, str_value = 5, ref_value = 7

def _plane_name(plane) -> str:
    return next((_text(v) for n, v in fields(plane) if n == 2), "")


def _map_values(plane, number: int) -> Iterator[memoryview]:
    for n, entry in fields(plane):
        if n == number:
            for k, v in fields(entry):
                if k == 2:
                    yield v


def _stat(stat, names: Dict[int, str]):
    """(name, value) of an XStat; a ``ref_value`` names a stat
    metadata whose name is the string."""
    name, value = None, None
    for n, v in fields(stat):
        if n == 1:
            name = names.get(v)
        elif n in (3, 4):
            value = v
        elif n == 5:
            value = _text(v)
        elif n == 7:
            value = names.get(v, "")
    return name, value


def scope_path(op_name: str) -> Tuple[str, ...]:
    """``jit(f)/op:Join/join:lookup/gather:`` is
    ``("op:Join", "join:lookup")``: from the first operator scope, the
    parts that are scopes of ours (``<family>:<Name>``; a primitive
    ends in a colon, a transform is ``jit(...)`` or ``jvp(...)``)."""
    parts = op_name.split("/")
    first = next((i for i, p in enumerate(parts) if p.startswith(OPERATOR)),
                 None)
    if first is None:
        return ()
    return tuple(p for p in parts[first:-1]
                 if ":" in p and not p.endswith(":") and "(" not in p)


def read(path: str) -> Optional[List[ScopedOp]]:
    """Chip 0's operations with module and scope path, by start; None
    where the trace cannot say (module docstring)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for n, plane in fields(space):
        if n == 1:
            name = _plane_name(plane)
            tail = name[len(xplane.DEVICE_PLANE):].split()[:1]
            if name.startswith(xplane.DEVICE_PLANE) and tail \
                    and tail[0].isdigit():
                planes[int(tail[0])] = plane
    if not planes:
        return None
    plane = planes[min(planes)]
    stat_names = {}
    for meta in _map_values(plane, 5):
        got = dict(fields(meta))
        stat_names[got.get(1, 0)] = _text(got.get(2, b""))
    op_names: Dict[int, Tuple[str, Optional[int]]] = {}  # id -> tf_op, program
    event_names: Dict[int, str] = {}
    for meta in _map_values(plane, 4):
        ident, tf_op, program = 0, "", None
        for n, v in fields(meta):
            if n == 1:
                ident = v
            elif n == 2:
                event_names[ident] = _text(v)
            elif n == 5:
                name, value = _stat(v, stat_names)
                if name == "tf_op":
                    tf_op = value
                elif name == "program_id":
                    program = value
        op_names[ident] = (tf_op, program)
    if not any(OPERATOR in tf_op for tf_op, _ in op_names.values()):
        return None
    lines = {}
    for n, line in fields(plane):
        if n == 3:
            got = {k: v for k, v in fields(line) if k in (2, 3)}
            lines[_text(got.get(2, b""))] = (line, got.get(3, 0))
    if xplane.OPS_LINE not in lines:
        return None
    modules: Dict[int, str] = {}  # program id -> "jit_<function>"
    if xplane.MODULES_LINE in lines:
        for n, event in fields(lines[xplane.MODULES_LINE][0]):
            if n == 4:
                name = event_names.get(dict(fields(event)).get(1, 0), "")
                head, _, tail = name.rpartition("(")
                if tail.rstrip(")").isdigit():
                    modules[int(tail.rstrip(")"))] = head
    line, t0_ns = lines[xplane.OPS_LINE]
    out = []
    paths: Dict[int, Tuple[str, Tuple[str, ...]]] = {}
    for n, event in fields(line):
        if n != 4:
            continue
        got = dict(fields(event))
        ident = got.get(1, 0)
        if ident not in paths:
            tf_op, program = op_names.get(ident, ("", None))
            paths[ident] = (modules.get(program, ""), scope_path(tf_op))
        start = (_signed(t0_ns) + got.get(2, 0) / 1e3) / 1e9
        out.append(ScopedOp(start, start + got.get(3, 0) / 1e12, *paths[ident]))
    out.sort(key=lambda o: o.start)
    return out


# -- what the readers ask ---------------------------------------------------

_read_cached = functools.lru_cache(maxsize=1)(read)  # once per process


def for_run(run) -> Optional[List[ScopedOp]]:
    """The scoped operations of ``run``'s trace, found by the rule of
    ``run.py`` (``trace_out/<cell>``) and read once per process."""
    if run.trace is None or not run.pass_intervals:
        return None
    path = xplane.find_xplane(os.path.join(TRACE_OUT, run.cell.name))
    if path is None:
        return None
    return _read_cached(path)


def busy_s(ops: Sequence[ScopedOp], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which one of ``ops`` ran."""
    return xplane.covered(xplane.union([(o.start, o.end) for o in ops]),
                          lo, hi)


def under(ops: Sequence[ScopedOp], *scopes: str,
          depth: Optional[int] = 0) -> List[ScopedOp]:
    """The operations whose scope at ``depth`` (None: at any depth) is
    one of ``scopes``."""
    if depth is None:
        return [o for o in ops if any(s in o.scopes for s in scopes)]
    return [o for o in ops
            if len(o.scopes) > depth and o.scopes[depth] in scopes]


def ms_per_pass(run, *scopes: str, depth: Optional[int] = 0):
    """Median over the traced passes of chip 0's busy milliseconds
    under ``scopes``; None where the trace has no scopes."""
    from benchmark import stats

    ops = for_run(run)
    if ops is None:
        return None
    mine = under(ops, *scopes, depth=depth)
    return stats.median([busy_s(mine, *span) * 1e3
                         for span in run.pass_intervals])
