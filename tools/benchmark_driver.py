#!/usr/bin/env python
"""Benchmark driver: run SQL suites and report wall-clock percentiles.

Reference analog: ``presto-benchmark-driver`` (BenchmarkDriver.java +
docs presto-docs/src/main/sphinx/installation/benchmark-driver.rst) —
a CLI that executes named query suites against an engine and prints
per-query wall/cpu statistics (median, mean, stddev).

Suites are directories of ``.sql`` files (the layout of
presto-benchto-benchmarks/src/main/resources/sql/presto/tpch/) or the
built-in ``tpch``/``tpcds`` corpora from tests/.

Usage:
  python tools/benchmark_driver.py --suite tpch --sf 0.01 --runs 3
  python tools/benchmark_driver.py --suite path/to/dir --catalog tpch
  python tools/benchmark_driver.py --suite tpch --queries q1,q6 --json
  python tools/benchmark_driver.py --suite tpch --streams 4 --runs 2
  python tools/benchmark_driver.py --queries q1,q6,q14 --task-concurrency 4

``--streams N`` switches to concurrent-query THROUGHPUT mode: N client
threads issue the query against the same warm engine and the report
carries aggregate rows/s plus p50/p95 per-execution latency — the
cross-query behavior of the split scheduler measured, not assumed.
``--task-concurrency`` pins the morsel scheduler width for A/B legs
(1 = the serial baseline).

``--hot-cold H:C`` (with ``--streams``) runs the serving-tier workload
mix: of every H+C executions per client, H repeat the suite query
verbatim (the hot dashboard set) and C run a UNIQUE structurally
distinct cold variant — reporting per-class p50/p95 and the result-
cache hit rate.  ``--result-cache on`` enables the structural result
cache (the A/B lever for PERF.md), ``--admit N`` routes every
execution through a serving-tier AdmissionController with per-group
hard concurrency N so enforced limits are part of what's measured.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_suite(name: str):
    """-> list of (query_name, sql)."""
    if os.path.isdir(name):
        out = []
        for fn in sorted(os.listdir(name)):
            if fn.endswith(".sql"):
                with open(os.path.join(name, fn)) as f:
                    out.append((fn[:-4], f.read()))
        if not out:
            raise SystemExit(f"no .sql files in {name}")
        return out
    if name == "tpch":
        from tests.tpch_queries import QUERIES

        return [(f"q{i}", sql) for i, sql in sorted(QUERIES.items())]
    if name == "tpcds":
        from tests.tpcds_queries import QUERIES

        return [(f"q{i}", sql) for i, sql in sorted(QUERIES.items())]
    raise SystemExit(f"unknown suite {name!r} (builtin: tpch, tpcds)")


def build_runner(args, programs=None):
    from presto_tpu.catalog import Catalog
    from presto_tpu.runner import QueryRunner

    catalog = Catalog()
    if args.suite == "tpcds" or args.catalog == "tpcds":
        from presto_tpu.connectors.tpcds import Tpcds

        catalog.register("tpcds", Tpcds(sf=args.sf))
    else:
        from presto_tpu.connectors.tpch import Tpch

        catalog.register("tpch", Tpch(sf=args.sf))
    return QueryRunner(catalog, programs=programs)


# the standing cold-start protocol (VERDICT checklist #1): scan-heavy
# q6, join+agg q14, wide-agg q1, join-order-sensitive q3 — in that
# order, so cross-query program reuse is part of what's measured
COLD_SEQUENCE = ("q6", "q14", "q1", "q3")


def cold_compile_report(args):
    """--cold-compile-report: run COLD_SEQUENCE with cold in-process
    caches and write per-query warmup seconds + compiled-program
    counts to COMPILE_REPORT.json (the report names the backend it
    ran on)."""
    import jax

    from presto_tpu.exec.programs import (
        ProgramRegistry, enable_persistent_cache,
        persistent_cache_stats,
    )

    suite = dict(load_suite(args.suite))
    names = list(args.queries.split(",")) if args.queries \
        else list(COLD_SEQUENCE)
    missing = [n for n in names if n not in suite]
    if missing:
        raise SystemExit(f"unknown queries {missing}")

    jax.clear_caches()  # cold in-process compile caches
    cache_dir = enable_persistent_cache()
    registry = ProgramRegistry()
    runner = build_runner(args, programs=registry)

    queries = []
    prev_programs = prev_compile = 0.0
    for name in names:
        t0 = time.perf_counter()
        res = runner.execute(suite[name])
        warmup = time.perf_counter() - t0
        t0 = time.perf_counter()
        runner.execute(suite[name])
        warm = time.perf_counter() - t0
        s = registry.stats()
        queries.append({
            "query": name,
            "rows": len(res),
            "warmup_s": round(warmup, 3),
            "warm_s": round(warm, 4),
            "programs_total": s["programs"],
            "programs_new": s["programs"] - int(prev_programs),
            "compile_s_new": round(s["compile_s"] - prev_compile, 3),
            "registry_hits": s["hits"],
            "registry_misses": s["misses"],
        })
        prev_programs, prev_compile = s["programs"], s["compile_s"]
        print(f"{name:>6}  warmup={warmup:.2f}s warm={warm:.3f}s "
              f"programs={s['programs']} (+{queries[-1]['programs_new']})",
              flush=True)

    report = {
        "sequence": names,
        "sf": args.sf,
        "backend": jax.default_backend(),
        "persistent_cache_dir": cache_dir,
        "total_warmup_s": round(sum(q["warmup_s"] for q in queries), 3),
        "distinct_programs": int(prev_programs),
        "registry": registry.stats(),
        "persistent": persistent_cache_stats(),
        "queries": queries,
    }
    out = args.report_out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "COMPILE_REPORT.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {out}: {report['distinct_programs']} distinct programs, "
          f"total warmup {report['total_warmup_s']}s", flush=True)
    return 0


def run_streams(runner, name: str, sql: str, streams: int, runs: int):
    """Concurrent-query throughput: ``streams`` client threads each
    execute ``sql`` ``runs`` times against the shared warm engine.
    Returns the aggregate row/s + latency-percentile report row."""
    import statistics as stats
    import threading

    warm = runner.execute(sql)
    latencies: list = []
    rows_total = [0]
    errors: list = []
    lock = threading.Lock()

    def client():
        for _ in range(runs):
            t0 = time.perf_counter()
            try:
                res = runner.execute(sql)
            except Exception as e:  # a failing stream must be visible
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)
                rows_total[0] += len(res)

    # client-count is CLI-derived (--streams), not hard-coded
    threads = [threading.Thread(target=client, name=f"stream-{i}")
               for i in range(streams)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if not latencies:
        return {"query": name, "streams": streams,
                "error": errors[0] if errors else "no executions"}
    lat = sorted(latencies)

    def pct(p):
        # nearest-rank (ceil, 1-indexed): floor-indexing returned the
        # MAX for any n <= 20, making "p95" a worst-case outlier report
        return _percentile(lat, p)

    row = {
        "query": name,
        "streams": streams,
        "runs_per_stream": runs,
        "executions": len(lat),
        "rows": len(warm),
        "wall_s": round(wall, 3),
        "queries_per_s": round(len(lat) / wall, 3),
        "rows_per_s": round(rows_total[0] / wall, 1),
        "p50_s": round(stats.median(lat), 4),
        "p95_s": round(pct(95), 4),
        "max_s": round(lat[-1], 4),
    }
    if errors:
        row["errors"] = errors
    return row


def _top_finding(res):
    """The doctor's top-ranked finding riding a MaterializedResult
    (runner attaches the full ranked list), trimmed to what the report
    needs — bench_compare.py prints it next to flagged regressions."""
    findings = getattr(res, "findings", None)
    if not findings:
        return None
    top = findings[0]
    return {"rule": top["rule"], "score": top["score"],
            "summary": top["summary"]}


def _percentile(sorted_vals, p):
    """Nearest-rank percentile (ceil, 1-indexed) — run_streams' pct."""
    import math

    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1,
                           max(0, math.ceil(p / 100.0 * len(sorted_vals)) - 1))]


def _cold_variant(sql: str, uid: int) -> str:
    """A structurally distinct sibling of ``sql``: a huge, unique LIMIT
    changes the plan shape (TopN/Limit count is part of the structural
    signature) without changing the rows — so cold variants can never
    hit the hot entry yet stay oracle-comparable."""
    base = sql.strip().rstrip(";")
    if " limit " in base.lower():
        return f"SELECT * FROM ({base}) cold_{uid} LIMIT {9_000_000 + uid}"
    return f"{base} LIMIT {9_000_000 + uid}"


def run_hot_cold(runner, name: str, sql: str, streams: int, runs: int,
                 mix: str, admit: int = 0):
    """Serving-tier workload mix: each of ``streams`` clients runs
    ``runs`` executions scheduled hot:cold by ``mix`` (e.g. ``3:1``).
    Hot = the query verbatim (result-cache candidates); cold = unique
    structural variants (guaranteed misses).  Reports per-class p50/p95
    and the result-cache hit rate over the run; ``--admit N`` funnels
    every execution through an AdmissionController so per-group limits
    are enforced while the percentiles are measured."""
    import statistics as stats
    import threading

    from presto_tpu.obs import METRICS

    h, c = (int(x) for x in mix.split(":"))
    if h <= 0 or c < 0:
        raise SystemExit(f"bad --hot-cold mix {mix!r} (use e.g. 3:1)")
    ctl = None
    if admit > 0:
        from presto_tpu.resource_groups import (
            ResourceGroup, ResourceGroupManager,
        )
        from presto_tpu.serving import AdmissionController

        ctl = AdmissionController(
            ResourceGroupManager(ResourceGroup(
                "bench", hard_concurrency=admit, max_queued=10_000)),
            pool=runner.memory_pool)
    warm = runner.execute(sql)  # plan + compile out of the measurement
    snap0 = dict(METRICS.snapshot())
    lock = threading.Lock()
    lat = {"hot": [], "cold": []}
    queue_waits: list = []
    errors: list = []
    uid_counter = [0]

    def client(ci: int):
        for k in range(runs):
            hot = (k % (h + c)) < h
            if hot:
                stmt = sql
            else:
                with lock:
                    uid_counter[0] += 1
                    uid = uid_counter[0]
                stmt = _cold_variant(sql, uid)
            ticket = None
            t0 = time.perf_counter()
            try:
                if ctl is not None:
                    ticket = ctl.admit(f"{name}-{ci}-{k}", "bench",
                                       timeout=300.0, statement_key=stmt)
                    queue_waits.append(ticket.queued_ms())
                res = runner.execute(stmt)
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return
            finally:
                if ctl is not None:
                    ctl.release(ticket)
            dt = time.perf_counter() - t0
            with lock:
                lat["hot" if hot else "cold"].append(dt)
                del res

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"hotcold-{i}")
               for i in range(streams)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap1 = dict(METRICS.snapshot())

    def delta(metric):
        return snap1.get(metric, 0.0) - snap0.get(metric, 0.0)

    hits, misses = delta("cache.result_hits"), delta("cache.result_misses")
    row = {
        "query": name,
        "streams": streams,
        "mix": mix,
        "rows": len(warm),
        "executions": len(lat["hot"]) + len(lat["cold"]),
        "wall_s": round(wall, 3),
        "queries_per_s": round(
            (len(lat["hot"]) + len(lat["cold"])) / wall, 3) if wall else None,
        "cache_result_hits": int(hits),
        "cache_result_misses": int(misses),
        "cache_hit_rate": (round(hits / (hits + misses), 3)
                           if hits + misses else None),
    }
    for cls in ("hot", "cold"):
        vals = sorted(lat[cls])
        row[cls] = {
            "executions": len(vals),
            "p50_s": round(stats.median(vals), 4) if vals else None,
            "p95_s": round(_percentile(vals, 95), 4) if vals else None,
            "max_s": round(vals[-1], 4) if vals else None,
        }
    if ctl is not None:
        qw = sorted(queue_waits)
        row["admit_concurrency"] = admit
        row["queue_wait_p50_ms"] = round(_percentile(qw, 50), 2) if qw else None
        row["queue_wait_p95_ms"] = round(_percentile(qw, 95), 2) if qw else None
    if errors:
        row["errors"] = errors[:5]
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", default="tpch",
                    help="builtin suite name (tpch/tpcds) or a directory of .sql files")
    ap.add_argument("--catalog", default=None,
                    help="builtin catalog to register for directory suites")
    ap.add_argument("--sf", type=float, default=0.01, help="generator scale factor")
    ap.add_argument("--runs", type=int, default=3, help="timed runs per query (after 1 warmup)")
    ap.add_argument("--repeat", type=int, default=3,
                    help="independent repeats of the timed block; the "
                         "report carries median-of-medians ± spread and "
                         "every raw time (variance protocol)")
    ap.add_argument("--queries", default=None, help="comma list filter, e.g. q1,q6")
    ap.add_argument("--streams", type=int, default=0,
                    help="concurrent-query throughput mode: N client "
                         "threads over the same warm engine (aggregate "
                         "rows/s + p50/p95 latency)")
    ap.add_argument("--task-concurrency", type=int, default=0,
                    help="pin the morsel split-scheduler width for this "
                         "run (session task_concurrency; 1 = serial A/B "
                         "leg, 0 = process default)")
    ap.add_argument("--hot-cold", default=None, metavar="MIX",
                    help="with --streams: hot:cold execution mix per "
                         "client (e.g. 3:1) — repeating hot queries + "
                         "unique cold variants, per-class p50/p95 and "
                         "result-cache hit rate")
    ap.add_argument("--result-cache", default=None, choices=["on", "off"],
                    help="enable/disable the structural result cache "
                         "for this run (default: on for --hot-cold, "
                         "off otherwise)")
    ap.add_argument("--admit", type=int, default=0,
                    help="route every execution through a serving-tier "
                         "AdmissionController with this per-group hard "
                         "concurrency (0 = no admission gate)")
    ap.add_argument("--json", action="store_true", help="one JSON line per query")
    ap.add_argument("--cold-compile-report", action="store_true",
                    help="run the cold q6>q14>q1>q3 sequence and write "
                         "COMPILE_REPORT.json (warmup seconds + program counts)")
    ap.add_argument("--report-out", default=None,
                    help="output path for --cold-compile-report")
    args = ap.parse_args()

    import presto_tpu  # noqa: F401  (x64 etc.)

    if args.cold_compile_report:
        sys.exit(cold_compile_report(args))

    suite = load_suite(args.suite)
    if args.queries:
        want = set(args.queries.split(","))
        suite = [(n, q) for n, q in suite if n in want]
        if not suite:
            raise SystemExit(f"no queries match {args.queries!r}")

    runner = build_runner(args)
    if args.task_concurrency:
        runner.execute(
            f"SET SESSION task_concurrency = {args.task_concurrency}")
    cache_mode = args.result_cache or ("on" if args.hot_cold else None)
    if cache_mode is not None:
        runner.execute("SET SESSION result_cache_enabled = "
                       + ("true" if cache_mode == "on" else "false"))

    if args.hot_cold and not args.streams:
        raise SystemExit("--hot-cold requires --streams N")

    if args.streams:
        results = []
        for name, sql in suite:
            try:
                if args.hot_cold:
                    row = run_hot_cold(runner, name, sql, args.streams,
                                       max(args.runs, 1), args.hot_cold,
                                       admit=args.admit)
                else:
                    row = run_streams(runner, name, sql, args.streams,
                                      max(args.runs, 1))
            except Exception as e:
                row = {"query": name, "error": f"{type(e).__name__}: {e}"}
            results.append(row)
            if args.json:
                print(json.dumps(row), flush=True)
            elif "error" in row:
                print(f"{name:>8}  ERROR {row['error']}", flush=True)
            elif args.hot_cold:
                hr = row.get("cache_hit_rate")
                print(f"{name:>8}  mix={row['mix']} "
                      f"hot p50={row['hot']['p50_s']}s "
                      f"p95={row['hot']['p95_s']}s | "
                      f"cold p50={row['cold']['p50_s']}s "
                      f"p95={row['cold']['p95_s']}s | "
                      f"hit rate={'n/a' if hr is None else hr}"
                      + (f" | queue p95={row['queue_wait_p95_ms']}ms"
                         if "queue_wait_p95_ms" in row else ""),
                      flush=True)
            else:
                print(f"{name:>8}  streams={row['streams']} "
                      f"qps={row['queries_per_s']:.2f} "
                      f"rows/s={row['rows_per_s']:.1f} "
                      f"p50={row['p50_s']:.3f}s p95={row['p95_s']:.3f}s",
                      flush=True)
        sys.exit(0 if all("error" not in r for r in results) else 1)

    results = []
    for name, sql in suite:
        try:
            t0 = time.perf_counter()
            # estimate-vs-actual: per-operator stats on the WARMUP run
            # only (session.set, not SET SESSION — an executor rebuild
            # here would discard the warmed compile caches, and the
            # per-page device sync must not perturb the timed runs).
            # The worst misestimate ratio rides the row so
            # bench_compare can print it next to a flagged regression.
            runner.session.set("collect_stats", True)
            try:
                res = runner.execute(sql)
            finally:
                runner.session.set("collect_stats", False)
            warmup = time.perf_counter() - t0
            # variance protocol (VERDICT weak #3): --repeat independent
            # measurement blocks of --runs timed runs each.  The
            # headline is the MEDIAN of per-repeat medians with the
            # spread across repeats, and every raw time is kept, so a
            # regression is distinguishable from host variance.
            raw: list = []
            repeat_medians = []
            last = res
            for _ in range(max(args.repeat, 1)):
                times = []
                for _ in range(args.runs):
                    t0 = time.perf_counter()
                    last = runner.execute(sql)
                    times.append(time.perf_counter() - t0)
                raw.append([round(t, 4) for t in times])
                repeat_medians.append(statistics.median(times))
            # the query doctor's top-ranked finding for the final timed
            # run (obs/doctor.py) — "why is this query slow" travels
            # with the number that says it is
            top = _top_finding(last)
            flat = [t for block in raw for t in block]
            spread = (max(repeat_medians) - min(repeat_medians)) / 2
            row = {
                "query": name,
                "rows": len(res),
                "warmup_s": round(warmup, 3),
                "median_s": round(statistics.median(repeat_medians), 4),
                "spread_s": round(spread, 4),
                "repeat_medians_s": [round(m, 4) for m in repeat_medians],
                "raw_times_s": raw,
                "mean_s": round(statistics.mean(flat), 4),
                "min_s": round(min(flat), 4),
                "max_s": round(max(flat), 4),
                "stddev_s": round(statistics.stdev(flat), 4) if len(flat) > 1 else 0.0,
            }
            if top is not None:
                row["doctor"] = top
            wr = getattr(res, "worst_estimate_ratio", None)
            if wr is not None:
                row["worst_estimate_ratio"] = round(float(wr), 2)
        except Exception as e:
            row = {"query": name, "error": f"{type(e).__name__}: {e}"}
        results.append(row)
        if args.json:
            print(json.dumps(row), flush=True)
        elif "error" in row:
            print(f"{name:>8}  ERROR {row['error']}", flush=True)
        else:
            doc = row.get("doctor")
            print(f"{name:>8}  rows={row['rows']:<8} "
                  f"median={row['median_s']:.4f}s ±{row['spread_s']:.4f} "
                  f"mean={row['mean_s']:.4f}s min={row['min_s']:.4f}s "
                  f"max={row['max_s']:.4f}s (warmup {row['warmup_s']:.1f}s)"
                  + (f"  doctor: {doc['rule']} ({doc['score']:.2f})"
                     if doc else ""),
                  flush=True)

    ok = [r for r in results if "error" not in r]
    if ok and not args.json:
        total = sum(r["median_s"] for r in ok)
        print(f"\n{len(ok)}/{len(results)} queries ok; total median wall {total:.2f}s")
    sys.exit(0 if len(ok) == len(results) else 1)


if __name__ == "__main__":
    main()
