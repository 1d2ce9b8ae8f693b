"""Benchmark entry point: TPC-H operator throughput on one TPU chip.

One process.  Prints ONE JSON line with the per-query rates and the
device they were measured on (``platform``, ``device_kind``, device
count).  It fails where the device is not a TPU and when any query
fails: a rate measured on one device is never printed under the name
of another, and there is no fallback.

Protocol (BASELINE.md): the reference publishes no absolute numbers —
its own harness (presto-benchmark BenchmarkSuite / HandTpchQuery1,
HandTpchQuery6) measures rows/s of the operator pipeline over TPC-H
data already in memory.  We mirror that: TPC-H tables are pre-loaded
into the HBM-resident memory connector (no host generation inside the
timed region), then Q6 (scan+filter+project), Q14 (join + CASE), Q1
(hash aggregation) and Q3 (hash join + grouped agg) run end-to-end
through the SQL engine; rate = lineitem rows / best wall seconds.

What is measured here, how often, and at which sizes is the next
``benchmark`` issue's to redefine (ROADMAP S0).

Env knobs: BENCH_SF (default 1.0), BENCH_ITERS (default 3),
BENCH_SPLIT_ROWS (default 2^23), BENCH_STREAMS (default 4, 0 = off),
BENCH_TPCDS (default 1, 0 = off).
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# cheapest program first (q6 < q14 < q1 < q3 in compile cost)
QUERY_NAMES = ("q6", "q14", "q1", "q3")

#: the columns the four queries read: what a deployment would keep
#: resident for them
TPCH_COLUMNS = {
    "lineitem": ["l_orderkey", "l_partkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_mktsegment"],
    "part": ["p_partkey", "p_type"],
}

#: peak HBM bandwidth in bytes/s by ``device_kind`` (Google Cloud
#: documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s per chip).  A
#: device that is not listed is an error, not a default.
HBM_PEAK_BYTES_PER_SEC = {
    "TPU v5 lite": 819e9,
}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_tpu() -> dict:
    """The device as jax reports it; exits non-zero unless it is a TPU."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device} (jax {jax.__version__})")
    if dev.platform != "tpu":
        sys.exit(f"no TPU: jax found {device}; refusing to measure")
    return device


def load_tpch(sf: float, split_rows: int):
    """Generate TPC-H at ``sf`` and make ``TPCH_COLUMNS`` resident on
    the device; returns (generator connector, memory connector)."""
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.connectors.tpch import Tpch

    tpch = Tpch(sf=sf, split_rows=split_rows)
    mem = MemoryConnector()
    for table, columns in TPCH_COLUMNS.items():
        mem.load_from(tpch, table, columns=columns)
    return tpch, mem


def _measure(sf: float, iters: int) -> dict:
    import presto_tpu  # noqa: F401  (enables x64)
    import jax

    from presto_tpu.catalog import Catalog
    from presto_tpu.exec.programs import enable_persistent_cache
    from presto_tpu.runner import QueryRunner

    device = require_tpu()
    if device["kind"] not in HBM_PEAK_BYTES_PER_SEC:
        sys.exit(f"no HBM peak on record for device_kind {device['kind']!r}")
    log(f"compile cache: {enable_persistent_cache()}")

    # Split granularity: one dispatch per split per chain; large splits
    # amortize dispatch+fold overhead (SF1 lineitem fits one 8M-row
    # split: 6M x 8 cols x 8B = 384MB vs 16GB HBM).
    split_rows = int(os.environ.get("BENCH_SPLIT_ROWS", str(1 << 23)))
    t0 = time.time()
    _, mem = load_tpch(sf, split_rows)
    lineitem_rows = mem.row_count("lineitem")
    log(f"loaded sf={sf}: lineitem={lineitem_rows} rows in {time.time()-t0:.1f}s")

    catalog = Catalog()
    catalog.register("mem", mem)
    runner = QueryRunner(catalog)

    from tests.tpch_queries import QUERIES  # the shared corpus

    bench_queries = {n: QUERIES[int(n[1:])] for n in QUERY_NAMES}

    # bytes the engine must stream from HBM per query (columns touched x
    # 8 bytes x rows) — the roofline denominator for bandwidth figures
    nrows = {t: mem.row_count(t) for t in TPCH_COLUMNS}
    bytes_scanned = {
        "q1": 7 * 8 * nrows["lineitem"],
        "q6": 4 * 8 * nrows["lineitem"],
        "q3": (4 * 8 * nrows["lineitem"] + 4 * 8 * nrows["orders"]
               + 2 * 8 * nrows["customer"]),
        "q14": 4 * 8 * nrows["lineitem"] + 2 * 8 * nrows["part"],
    }

    rates = {}
    device_side = {}
    raw_times = {}
    for name, sql in bench_queries.items():
        # perf_counter, not time.time(): the engine_lint wallclock
        # rule's contract — an NTP step must not be able to fake a
        # rate change in the variance evidence
        t0 = time.perf_counter()
        res = runner.execute(sql)  # warmup: compile + execute
        log(f"{name}: warmup {time.perf_counter()-t0:.2f}s, "
            f"{len(res)} rows")
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            runner.execute(sql)
            times.append(time.perf_counter() - t0)
        best = min(times)
        rates[name] = round(lineitem_rows / best, 1)
        # every raw repeat time ships with the result, so a rate
        # regression is distinguishable from host variance after the
        # fact
        raw_times[name] = [round(t, 4) for t in times]
        log(f"{name}: best {best:.3f}s -> {rates[name]:.3e} lineitem rows/s")
        # device-side attribution: same plan without the host
        # result materialization, plus bytes-scanned / time vs the
        # HBM roofline
        plan = runner.plan(sql)
        dts = []
        for _ in range(min(iters, 2)):
            t0 = time.perf_counter()
            page = runner.executor.run_to_page(plan)
            jax.block_until_ready(page)
            dts.append(time.perf_counter() - t0)
        dt = min(dts)
        device_side[name] = {
            "seconds": round(dt, 4),
            "rows_per_sec": round(lineitem_rows / dt, 1),
            "bytes": bytes_scanned[name],
            "gbps": round(bytes_scanned[name] / dt / 1e9, 2),
        }
        log(f"{name}: device {dt:.3f}s -> {device_side[name]['gbps']} GB/s")

    out = {
        "device": device,
        "sf": sf,
        "unit": "lineitem rows/s",
        "rates": rates,
        "raw_times": raw_times,
        "device_side": device_side,
        "hbm_peak_gbps": HBM_PEAK_BYTES_PER_SEC[device["kind"]] / 1e9,
    }

    # concurrent-stream throughput (the split scheduler's cross-query
    # behavior, measured not assumed): N client threads re-issuing q6
    # against the same warm engine.  BENCH_STREAMS=0 disables.
    n_streams = int(os.environ.get("BENCH_STREAMS", "4"))
    if n_streams > 0:
        sys.path.insert(0, os.path.join(HERE, "tools"))
        from benchmark_driver import run_streams

        out["streams"] = run_streams(
            runner, "q6", bench_queries["q6"], n_streams, 2)
        log(f"streams: {out['streams']}")
        if out["streams"].get("error") or out["streams"].get("errors"):
            sys.exit(f"stream executions failed: {out['streams']}")

    # TPC-DS star-schema rates (BASELINE.md protocol names Q3/Q7).
    # BENCH_TPCDS=0 disables.
    if os.environ.get("BENCH_TPCDS", "1") != "0":
        out["tpcds_unit"] = "store_sales rows/s"
        out["tpcds_rates"] = _measure_tpcds(min(sf, 1.0), iters, split_rows)
    return out


def _measure_tpcds(sf: float, iters: int, split_rows: int) -> dict:
    from presto_tpu.catalog import Catalog
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.connectors.tpcds import Tpcds
    from presto_tpu.runner import QueryRunner

    t0 = time.time()
    ds = Tpcds(sf=sf, split_rows=split_rows)
    mem = MemoryConnector()
    for t in ("store_sales", "date_dim", "item",
              "customer_demographics", "promotion"):
        mem.load_from(ds, t)
    ss_rows = mem.row_count("store_sales")
    log(f"tpcds sf={sf}: store_sales={ss_rows} rows in {time.time()-t0:.1f}s")
    catalog = Catalog()
    catalog.register("tpcds", mem)
    runner = QueryRunner(catalog)
    from tests.tpcds_queries import QUERIES as DS

    rates = {}
    for qn in (3, 7):
        name = f"ds_q{qn}"
        t0 = time.perf_counter()
        runner.execute(DS[qn])
        log(f"{name}: warmup {time.perf_counter()-t0:.2f}s")
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            runner.execute(DS[qn])
            times.append(time.perf_counter() - t0)
        rates[name] = round(ss_rows / min(times), 1)
        log(f"{name}: best {min(times):.3f}s -> "
            f"{rates[name]:.3e} store_sales rows/s")
    return rates


def main():
    sf = float(os.environ.get("BENCH_SF", "1.0"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    print(json.dumps(_measure(sf, iters)), flush=True)


if __name__ == "__main__":
    main()
