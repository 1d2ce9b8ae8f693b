"""chip_smoke.py — the quickest proof that the served path still starts
on the chip.

One process, the normal entry points, no fallback:

1. Device: requires ``jax.devices()[0].platform == "tpu"``.
2. Deployment: TPC-H generated by ``connectors/tpch.py`` from its fixed
   seed and made resident in HBM through ``MemoryConnector.load_from``
   with bench.py's columns.  The deployment is SF10 in 2^23-row splits
   (BASELINE.json "TPC-H SF10 Q6" / "SF10 Q3"; ROADMAP R1); the default
   run is cut to SF1 because the smoke must finish inside 1200 s and,
   on one v5e chip, loading SF10 and compiling its programs cold takes
   longer than that (PERF.md, "Bring-up on v5e").  The split size is
   cut with the scale, so a reduced run keeps the deployment's layout:
   several splits per chain, a ragged last one, more than one wave on
   four chips.  Row widths, key distributions and query text are the
   source's at every scale.
3. Served path, one chip: ``CoordinatorServer(QueryRunner(catalog))``
   on loopback answers Q6, Q14, Q1 and Q3 (tests/tpch_queries.py)
   submitted through ``StatementClient``, each once cold and once
   warm, rows out on the host.
4. Answers: every result is compared with the hand-written pandas
   programs of tests/pandas_oracle.py on the same generated data,
   under tests/oracle.assert_rows_match's tolerances.
5. Four chips (where jax reports at least four devices): the same
   tables and queries with ``SET SESSION distributed = true``; every
   result must come from the mesh tier (no fallback to the local
   executor, at least one mesh stage) and equal the one-chip rows.

The first failure raises and the exit code is non-zero; nothing is
printed to standard output then.  On success standard output is two
lines, one JSON object each.  The first holds the smoke's observations:
the device, the scale, ``reduced`` (every cut of scale), and per phase
and query what was seen.  Those are observations of one run, not
benchmark metrics.  The last line is the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it.

    python chip_smoke.py              # on the chip: SF1, inside 1200 s
    python chip_smoke.py --sf 10      # the full deployment, about 30 min cold
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --sf 0.01

``--cpu-rehearsal`` exists to debug the script itself where there is no
chip; its output says ``"platform": "cpu"`` and it is never the default.
"""

import argparse
import json
import math
import sys
import time
from decimal import Decimal

FULL_SF = 10.0  # the deployment
DEFAULT_SF = 1.0  # what the 1200 s limit leaves room for
FULL_SPLIT_ROWS = 1 << 23
QUERY_IDS = (6, 14, 1, 3)  # cheapest program first


def split_rows(sf: float) -> int:
    """The deployment's split size cut with the scale, to a power of
    two: every scale has the deployment's eight or so lineitem splits."""
    return 1 << round(math.log2(FULL_SPLIT_ROWS * sf / FULL_SF))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_memory():
    """``bytes_in_use`` / ``peak_bytes_in_use`` per device (None where
    the backend reports no memory statistics, as XLA:CPU does)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if stats is None else {
            "bytes_in_use": stats["bytes_in_use"],
            "peak_bytes_in_use": stats["peak_bytes_in_use"]})
    return out


def decode_rows(columns, rows):
    """The statement protocol ships decimals as strings; make them
    Decimals again so they compare by value."""
    is_decimal = [c["type"].startswith("decimal") for c in columns]
    return [tuple(Decimal(v) if dec and v is not None else v
                  for v, dec in zip(row, is_decimal)) for row in rows]


def submit(client, sql):
    """One statement through the client; (rows, final stats, seconds)."""
    pages = []
    t0 = time.perf_counter()
    columns, rows = client.execute(sql, on_progress=pages.append)
    seconds = time.perf_counter() - t0
    return decode_rows(columns, rows), pages[-1], seconds


def run_queries(client, registry, distributed):
    """Each query once cold and once warm; per-query observations and
    the warm rows."""
    from presto_tpu.exec.programs import persistent_cache_stats
    from tests.tpch_queries import QUERIES

    observations, answers = {}, {}
    for qid in QUERY_IDS:
        name = f"q{qid}"
        programs0, compile0 = registry.program_count(), registry.compile_s
        cache0 = persistent_cache_stats()
        cold_rows, cold_stats, cold_s = submit(client, QUERIES[qid])
        rows, stats, warm_s = submit(client, QUERIES[qid])
        if rows != cold_rows:
            raise AssertionError(f"{name}: warm rows differ from cold rows")
        if distributed:
            # DistributedRunner.run answers from the local executor on
            # DistributedUnsupported; that is not a mesh answer
            for s in (cold_stats, stats):
                if s.get("distFallback") is not None \
                        or not s.get("distStages", 0) >= 1:
                    raise AssertionError(
                        f"{name}: not answered by the mesh tier: {s}")
        cache1 = persistent_cache_stats()
        observations[name] = {
            "ok": True,
            "rows": len(rows),
            "cold_s": round(cold_s, 3),
            "warm_s": round(warm_s, 4),
            "programs_compiled": registry.program_count() - programs0,
            "compile_s": round(registry.compile_s - compile0, 3),
            "persistent_hits": (cache1["persistent_hits"]
                                - cache0["persistent_hits"]),
            "persistent_misses": (cache1["persistent_misses"]
                                  - cache0["persistent_misses"]),
            "dist_stages": stats.get("distStages"),
        }
        answers[name] = rows
        log(f"{name}: {observations[name]}")
    return observations, answers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help=f"TPC-H scale factor (default {DEFAULT_SF:g}); "
                         f"anything below {FULL_SF:g} is reported as "
                         "reduced")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="accept a non-TPU device (debugging the script)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}, jax {jax.__version__}")
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        log(f"chip_smoke: no TPU, jax found {device}")
        return 1

    import presto_tpu  # noqa: F401  (enables x64)
    from bench import TPCH_COLUMNS, load_tpch
    from presto_tpu.catalog import Catalog
    from presto_tpu.client import StatementClient
    from presto_tpu.exec.programs import (
        enable_persistent_cache, persistent_cache_stats,
    )
    from presto_tpu.runner import QueryRunner
    from presto_tpu.server.coordinator import CoordinatorServer
    from tests.oracle import assert_rows_match
    from tests.pandas_oracle import PANDAS_QUERIES, load_frames

    reduced = []
    if args.sf < FULL_SF:
        reduced.append(
            f"scale: TPC-H SF{FULL_SF:g} -> SF{args.sf:g} (the 1200 s "
            f"limit: at SF{FULL_SF:g} load and cold compile alone exceed it)")
        reduced.append(
            f"split rows: {FULL_SPLIT_ROWS} -> {split_rows(args.sf)} "
            "(cut with the scale; the split count per table is kept)")
    out = {"device": device, "jax": jax.__version__,
           "scale": f"TPC-H SF{args.sf:g}", "reduced": reduced,
           "compile_cache_dir": enable_persistent_cache()}
    log(f"compile cache: {out['compile_cache_dir']}")

    t0 = time.perf_counter()
    tpch, mem = load_tpch(args.sf, split_rows(args.sf))
    out["load"] = {
        "seconds": round(time.perf_counter() - t0, 1),
        "rows": {t: mem.row_count(t) for t in TPCH_COLUMNS},
        "splits": {t: mem.num_splits(t) for t in TPCH_COLUMNS},
        "device_memory": device_memory(),
    }
    log(f"load: {out['load']}")

    catalog = Catalog()
    catalog.register("mem", mem)
    runner = QueryRunner(catalog)
    registry = runner.executor.programs
    server = CoordinatorServer(runner)
    server.start()
    try:
        client = StatementClient(server.uri)
        one_chip, answers = run_queries(client, registry, distributed=False)
        out["one_chip"] = {"queries": one_chip,
                           "device_memory": device_memory()}

        # the reference: plain pandas programs over the generator's own
        # arrays, sharing nothing with the engine or the load path
        t0 = time.perf_counter()
        frames = load_frames(tpch, columns=TPCH_COLUMNS)
        for qid in QUERY_IDS:
            assert_rows_match(answers[f"q{qid}"], PANDAS_QUERIES[qid](frames),
                              ordered=False)
            log(f"q{qid}: equals the pandas reference")
        del frames
        out["reference_seconds"] = round(time.perf_counter() - t0, 1)

        if device["count"] >= 4:
            submit(client, "SET SESSION distributed = true")
            four_chip, dist_answers = run_queries(
                client, registry, distributed=True)
            for name, rows in dist_answers.items():
                # all four queries either order their rows or return one
                assert_rows_match(rows, answers[name], ordered=True)
                log(f"{name}: four-chip rows equal the one-chip rows")
            out["four_chip"] = {"mesh_devices": device["count"],
                                "queries": four_chip,
                                "device_memory": device_memory()}
        else:
            log(f"four-chip phase not run: jax reports {device['count']} "
                "device(s)")
            out["four_chip"] = None
    finally:
        server.stop()

    out["programs"] = registry.stats()
    out["persistent_cache"] = persistent_cache_stats()
    print(json.dumps(out), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
