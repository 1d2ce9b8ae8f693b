"""One cell, one process, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. requires a TPU with at least the cell's chips, else exits non-zero
   with nothing on standard output (``--cpu-rehearsal`` accepts another
   device for the sandbox and the tier-1 tests; it prints
   ``"platform": "cpu"`` and is never the default);
2. places the compile cache by the program's one rule
   (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
3. loads the configuration's tables onto the device (``tables.py``);
4. starts ``CoordinatorServer(QueryRunner(catalog))`` on loopback, opens
   ``StatementClient``s and sets the layout's session properties;
5. warms up: every query of the cell twice, in the mix's own list order
   whatever the seed (the seed orders the window's passes, not the start
   of the process), each answer compared with the stored reference;
6. measures for ``--seconds`` (``loadgen.py``);
7. prints the observations (sample counts, per-query medians, phases)
   on one line and the contract's result object as the last line, what
   ``correct`` compared under its last key, ``compared``.

Everything up to the start of (6) is ``setup_s``.  With ``--trace 0``
the metrics are the end-to-end ones; with ``--trace 1`` a few passes of
the window run inside ``jax.profiler.trace`` and the metrics are the
per-layer ones, one reader file each under ``layer_metrics/``.
"""

import time

T_START = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import loadgen, specs, stats, xplane  # noqa: E402
from benchmark.reference import rows_match  # noqa: E402

#: the traced stretch: whole passes of client 0, ended at the first pass
#: boundary with at least TRACE_MIN_PASSES passes and TRACE_MIN_S
#: seconds, or TRACE_MAX_S seconds, inside it
TRACE_SKIP_PASSES = 1
TRACE_MIN_PASSES = 3
TRACE_MIN_S = 2.0
TRACE_MAX_S = 8.0
#: under this many passes no tail is reported (a p95 of 12 is a maximum)
TAIL_MIN_PASSES = 200


def log(*a):
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""

    cell: specs.Cell
    device: dict
    peaks: Optional[dict]  # peaks.json's entry for the device kind
    row_counts: Dict[str, int]
    phases: Dict[str, float]  # load_s, table_bytes, first_call_s, ...
    passes: List[loadgen.Pass]  # the window's completed passes
    counters: Dict[str, int]  # deltas over the window
    trace: Optional[xplane.Trace] = None
    traced: List[loadgen.Pass] = dataclasses.field(default_factory=list)

    def pass_interval(self, p: loadgen.Pass):
        """[start, end] of a traced pass on the trace's clock, from the
        marks of its first and last statement; None where a mark is
        missing."""
        marks = [self.trace.marks.get(mark_name(q)) for q in p.queries]
        if not marks or any(m is None for m in marks):
            return None
        return marks[0][0], marks[-1][1]

    @functools.cached_property
    def pass_intervals(self) -> list:
        """The traced passes' intervals, those with every mark found."""
        if self.trace is None:
            return []
        return [s for s in map(self.pass_interval, self.traced) if s]

    def stretch(self):
        """The traced stretch on the trace's clock."""
        spans = self.pass_intervals
        return (spans[0][0], spans[-1][1]) if spans else None

    @functools.cached_property
    def busy(self) -> Dict[int, list]:
        """chip -> the merged intervals in which an operation ran."""
        if self.trace is None:
            return {}
        return {c: xplane.busy(ops) for c, ops in self.trace.chips.items()}

    def busy_s_per_pass(self) -> List[float]:
        """Seconds the first chip was busy inside each traced pass."""
        if not self.busy:
            return []
        merged = self.busy[min(self.busy)]
        return [xplane.covered(merged, *span) for span in self.pass_intervals]

    def busy_shares(self) -> Dict[int, float]:
        """chip -> its busy share of the traced stretch."""
        stretch = self.stretch()
        if stretch is None:
            return {}
        lo, hi = stretch
        return {c: xplane.covered(merged, lo, hi) / (hi - lo)
                for c, merged in self.busy.items()}


def mark_name(q: loadgen.QueryRecord) -> str:
    return f"{xplane.MARK}{q.name}:{q.seq}"


def layer_metric_readers(root: str = HERE) -> list:
    """Every ``layer_metrics/<name>.py``: a module with NAME, UNIT,
    optionally WORKLOADS (cell names; absent or None means every cell)
    and ``read(run)``, which returns a number or None."""
    directory = os.path.join(root, "layer_metrics")
    return [specs.module_from_file(os.path.join(directory, f))
            for f in sorted(os.listdir(directory))
            if f.endswith(".py") and not f.startswith("_")]


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip; 0 where the backend keeps no memory
    statistics (XLA:CPU, the rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Tracing:
    """Starts ``jax.profiler`` at a pass boundary of client 0 and stops
    it at a later one (module constants above)."""

    def __init__(self, directory: str):
        self.directory = directory
        self.first = None  # index of the first traced pass
        self.last = None  # one past the last
        self.t0 = 0.0

    def boundary(self, client: int, passes_done: int) -> None:
        import jax

        if client != 0 or self.last is not None:
            return
        now = time.perf_counter()
        if self.first is None:
            if passes_done >= TRACE_SKIP_PASSES:
                shutil.rmtree(self.directory, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(self.directory,
                                         profiler_options=options)
                self.first, self.t0 = passes_done, time.perf_counter()
            return
        n = passes_done - self.first
        if n >= 1 and ((n >= TRACE_MIN_PASSES and now - self.t0 >= TRACE_MIN_S)
                       or now - self.t0 >= TRACE_MAX_S):
            jax.profiler.stop_trace()
            self.last = passes_done

    def close(self) -> None:
        import jax

        if self.first is not None and self.last is None:
            jax.profiler.stop_trace()
            self.last = 1 << 60

    def covers(self, p: loadgen.Pass) -> bool:
        return (p.client == 0 and self.first is not None
                and self.first <= p.index < (self.last or 0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="accept a device that is not a TPU (debugging "
                         "the benchmark itself; says \"platform\": \"cpu\")")
    args = ap.parse_args(argv)

    cell = specs.load_cell(args.workload, HERE)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device: {device}, jax {jax.__version__}; cell {cell.name}")
    if device["platform"] != "tpu" and not args.cpu_rehearsal:
        log(f"benchmark: no TPU, jax found {device}")
        return 1
    if device["count"] < cell.chips:
        log(f"benchmark: {cell.name} needs {cell.chips} chips, jax found "
            f"{device['count']}")
        return 1
    all_peaks = specs.read_json(HERE, "peaks.json")
    peaks = all_peaks.get(device["kind"])
    if peaks is None and not args.cpu_rehearsal:
        log(f"benchmark: no peaks on record for device kind "
            f"{device['kind']!r} (benchmark/peaks.json)")
        return 1

    import presto_tpu  # noqa: F401  (enables x64)
    from presto_tpu import obs
    from presto_tpu.catalog import Catalog
    from presto_tpu.client import StatementClient
    from presto_tpu.exec.programs import (
        enable_persistent_cache, persistent_cache_stats,
    )
    from presto_tpu.runner import QueryRunner
    from presto_tpu.server.coordinator import CoordinatorServer

    from benchmark import tables

    log(f"compile cache: {enable_persistent_cache()}")

    phases: Dict[str, float] = {}
    t0 = time.perf_counter()
    mem, row_counts, load_phases = tables.load(cell.config, HERE, log)
    phases["load_s"] = time.perf_counter() - t0
    phases.update({"load_" + k: v for k, v in load_phases.items()})
    phases["table_bytes"] = float(sum(
        (d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices))
    log(f"load: {phases}")

    catalog = Catalog()
    catalog.register("mem", mem)
    runner = QueryRunner(catalog)
    registry = runner.executor.programs
    server = CoordinatorServer(runner)
    server.start()
    tracing = Tracing(os.path.join(HERE, "trace_out", cell.name)) \
        if args.trace else None
    seq = itertools.count(1)  # next() is atomic under the interpreter lock
    try:
        mix = cell.traffic
        n_clients = int(mix["clients"])
        clients = [StatementClient(server.uri) for _ in range(n_clients)]
        session = dict(cell.layout["session"])
        if args.trace:
            session["trace"] = "true"
        for key, value in session.items():
            clients[0].execute(f"SET SESSION {key} = {value}")
        statements = [loadgen.Statements(mix, cell.queries, args.seed, c)
                      for c in range(n_clients)]

        def submit(c: int, query: specs.Query, text: str):
            pages: list = []
            rec = loadgen.QueryRecord(query.name, next(seq), 0.0, 0.0, False)
            marked = (tracing is not None and tracing.first is not None
                      and tracing.last is None and c == 0)
            with (jax.profiler.TraceAnnotation(mark_name(rec)) if marked
                  else contextlib.nullcontext()):
                rec.t0 = time.perf_counter()
                try:
                    columns, rows = clients[c].execute(
                        text, on_progress=pages.append)
                    rows = rows_match.decode_rows(columns, rows)
                except Exception as e:  # a failed operation, counted
                    rec.why = f"{type(e).__name__}: {e}"
                rec.client_ms = (time.perf_counter() - rec.t0) * 1e3
            if rec.why is not None:
                return rec
            rec.stats = pages[-1] if pages else {}
            rec.query_id = clients[c].last_query_id
            rec.why = rows_match.mismatch(rows, query.expected, query.ordered)
            if rec.why is None and cell.layout["require_mesh"] and (
                    rec.stats.get("distFallback") is not None
                    or not rec.stats.get("distStages", 0) >= 1):
                rec.why = f"not answered by the mesh tier: {rec.stats}"
            rec.ok = rec.why is None
            if marked:
                tracer = obs.lookup(rec.query_id)
                if tracer is not None:
                    rec.spans = [(s.name, s.t0, s.t0 + s.dur)
                                 for s in list(tracer.spans)]
            return rec

        # warm-up: each query twice; the first call compiles or loads.
        # In the mix's list order for every seed: which statement a
        # process runs first sets where its buffers fall in HBM, and a
        # gather's time depends on that (PERF.md, PR 27, PR 33)
        cache0 = persistent_cache_stats()
        programs0 = registry.program_count()
        warm: List[loadgen.QueryRecord] = []
        first_call_s = 0.0
        for query in cell.queries:
            first = submit(0, query, statements[0].text(query))
            second = submit(0, query, statements[0].text(query))
            first_call_s += first.client_ms / 1e3
            warm += [first, second]
            log(f"warm-up {query.name}: first {first.client_ms / 1e3:.2f} s, "
                f"second {second.client_ms / 1e3:.3f} s"
                + "".join(f"; FAILED: {r.why}" for r in (first, second)
                          if not r.ok))
        cache1 = persistent_cache_stats()
        phases["first_call_s"] = first_call_s
        warm_up = {
            "programs": registry.program_count() - programs0,
            "persistent_hits": cache1["persistent_hits"] - cache0["persistent_hits"],
            "persistent_misses": cache1["persistent_misses"] - cache0["persistent_misses"],
        }
        log(f"warm-up: {warm_up}")

        def boundary(c: int, passes_done: int) -> None:
            if tracing is not None:
                tracing.boundary(c, passes_done)

        setup_s = time.perf_counter() - T_START
        window_t0, passes, records = loadgen.LOOPS[mix["loop"]](
            mix, statements, args.seconds, submit, boundary)
        cache2 = persistent_cache_stats()
        counters = {
            "programs": registry.program_count() - programs0 - warm_up["programs"],
            "persistent_hits": cache2["persistent_hits"] - cache1["persistent_hits"],
            "persistent_misses": cache2["persistent_misses"] - cache1["persistent_misses"],
        }
    finally:
        if tracing is not None:
            tracing.close()
        server.stop()

    good = [p for p in passes if p.ok]
    failed = [r for r in warm + records if not r.ok]
    for r in failed[:5]:
        log(f"FAILED {r.name} #{r.seq}: {r.why}")
    peak = memory_peak_bytes(devices)
    run = Run(cell, device, peaks, row_counts, phases, good, counters)
    device_out = dict(device, memory_peak_bytes=peak)
    breakdown = None

    if not args.trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        if good:
            end = max(p.end for p in good)
            rows_per_pass = sum(row_counts[t] for q in cell.queries
                                for t in q.reads)
            metrics["pass_p50_ms"] = {
                "value": stats.median([p.ms for p in good]), "unit": "ms"}
            metrics["rows_per_s"] = {
                "value": rows_per_pass * len(good) / (end - window_t0),
                "unit": "rows/s"}
        if peak:
            metrics["peak_hbm_gb"] = {"value": peak / 1e9, "unit": "GB"}
    else:
        path = xplane.find_xplane(tracing.directory)
        if path is not None:
            run.trace = xplane.read(path)
            run.traced = [p for p in good if tracing.covers(p)]
        stretch, shares = run.stretch(), run.busy_shares()
        device_out["busy_s"] = device_out["window_s"] = 0.0
        if stretch is not None and shares:
            lo, hi = stretch
            device_out["window_s"] = hi - lo
            device_out["busy_s"] = (hi - lo) * sum(shares.values()) / len(shares)
            breakdown = make_breakdown(run, lo, hi)
        metrics = {}
        for reader in layer_metric_readers():
            only = getattr(reader, "WORKLOADS", None)
            if only is not None and cell.name not in only:
                continue
            value = reader.read(run)
            if value is not None:
                metrics[reader.NAME] = {"value": value, "unit": reader.UNIT}

    by_query: Dict[str, list] = {}
    for p in good:
        for q in p.queries:
            by_query.setdefault(q.name, []).append(q)
    pass_ms = [p.ms for p in good]
    p25, p75 = stats.quartiles(pass_ms) or (None, None)
    observations = {
        "cell": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "passes": len(good),
        "traced_passes": len(run.traced),
        "warm_up_order": [q.name for q in cell.queries],
        "queries_per_pass": [q.name for q in statements[0].order],
        "setup_s": setup_s, "phases": phases, "warm_up": warm_up,
        "window_counters": counters,
        "pass_ms": {"p50": stats.median(pass_ms),
                    "p25": p25, "p75": p75,
                    "min": min(pass_ms, default=None),
                    "max": max(pass_ms, default=None),
                    "p95": (stats.percentile(pass_ms, 95)
                            if len(good) >= TAIL_MIN_PASSES else None)},
        "per_query_p50": {
            name: {"n": len(qs),
                   "client_ms": stats.median([q.client_ms for q in qs]),
                   "planning_ms": stats.median(
                       [q.stats.get("planningMs", 0.0) for q in qs]),
                   "execution_ms": stats.median(
                       [q.stats.get("executionMs", 0.0) for q in qs])}
            for name, qs in by_query.items()},
        "row_counts": row_counts,
        "memory": [m and {k: m[k] for k in ("bytes_in_use",
                                            "peak_bytes_in_use")}
                   for m in (d.memory_stats() for d in devices)],
    }
    result = {
        "correct": not failed and bool(good),
        "attempted": len(records),
        "failed": sum(1 for r in records if not r.ok),
        "metrics": metrics,
        "device": device_out,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # what ``correct`` compared, each number beside its limit: last in
    # the result's line and the last lines of standard error
    answers = len(warm) + len(records)
    result["compared"] = {
        "answers_wrong": {"value": len(failed), "limit": 0, "of": answers},
        "passes": {"value": len(good), "at_least": 1},
        "first_wrong": [f"{r.name} #{r.seq}: {r.why}"[:200]
                        for r in failed[:3]],
    }
    print(json.dumps(observations), flush=True)
    print(json.dumps(result), flush=True)
    log(f"compared: answers_wrong {len(failed)} (limit 0) of {answers} "
        f"answers, warm-up and window")
    log(f"compared: passes {len(good)} (at least 1)")
    return 0


def make_breakdown(run: Run, lo: float, hi: float) -> dict:
    """Chip 0's operations that took most time in the traced stretch,
    and its idle seconds by what the host was doing."""
    first = min(run.trace.chips)
    chip0 = run.trace.chips[first]
    queries, spans = [], []
    for p in run.traced:
        for q in p.queries:
            mark = run.trace.marks.get(mark_name(q))
            if mark is None:
                continue
            queries.append((q.name, mark[0], mark[1]))
            # the program's spans are on perf_counter; the mark began
            # at q.t0 there and at mark[0] on the trace's clock
            shift = mark[0] - q.t0
            spans += [(name, s + shift, e + shift) for name, s, e in q.spans
                      if name != "query"]
    idle = xplane.gaps(run.busy[first], lo, hi)
    return {
        "device_ops": [[n, s] for n, s in xplane.op_totals(chip0, lo, hi)],
        "idle_gaps": [[n, s] for n, s in
                      xplane.attribute_gaps(idle, queries, spans)],
    }


if __name__ == "__main__":
    sys.exit(main())
