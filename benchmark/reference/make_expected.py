"""Write ``benchmark/expected/<config>/<query>.json`` from the pandas
programs.

    python benchmark/reference/make_expected.py --config tpch_sf1

Generates the configuration's data with the generator it names, decodes
the resident columns into DataFrames (strings decoded, decimals scaled
to float, dates as int days), runs the pandas program of every query in
the configuration's query directory and writes the rows.  A benchmark
run compares against these files and never runs pandas.  Run by the
builder when a configuration or a query is added; wall time and peak
RSS are printed for PERF.md.  Touches neither the engine's executor nor
the benchmark's load path (``tables.load``): only the generator.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def programs() -> dict:
    """Every reference module's ``PROGRAMS`` (``reference/*.py`` that
    defines one), so a later PR adds a query's program as a new file."""
    from benchmark import specs

    out = {}
    for f in sorted(os.listdir(HERE)):
        if not f.endswith(".py") or f in ("__init__.py", "make_expected.py"):
            continue
        module = specs.module_from_file(os.path.join(HERE, f))
        out.update(getattr(module, "PROGRAMS", {}))
    return out


def load_frames(conn, tables: dict) -> dict:
    """Copied from ``tests/pandas_oracle.load_frames``."""
    import pandas as pd

    frames = {}
    for table, wanted in tables.items():
        schema = [(c, t) for c, t in conn.schema(table) if c in wanted]
        parts = []
        for split in range(conn.num_splits(table)):
            data = conn.generate_split(table, split)
            cols = {}
            for name, t in schema:
                arr = data[name]
                if t.is_string:
                    cols[name] = conn.dictionary_for(table, name).decode(arr)
                elif t.is_decimal:
                    cols[name] = arr / (10.0 ** t.scale)
                else:
                    cols[name] = arr
            parts.append(pd.DataFrame(cols))
        frames[table] = pd.concat(parts, ignore_index=True)
    return frames


def _plain(v):
    """numpy scalars to the Python values JSON round-trips exactly."""
    if hasattr(v, "item"):
        v = v.item()
    return v


def make(config_name: str, root: str, log=print) -> dict:
    from benchmark import specs, tables

    t0 = time.perf_counter()
    config = specs.read_json(root, "configs", config_name + ".json")
    conn = tables.generator_for(config, root)
    frames = load_frames(conn, config["tables"])
    frames_s = time.perf_counter() - t0
    qdir = os.path.join(root, "queries", config["queries"])
    out_dir = os.path.join(root, "expected", config_name)
    os.makedirs(out_dir, exist_ok=True)
    progs = programs()
    seconds = {}
    for f in sorted(os.listdir(qdir)):
        if not f.endswith(".sql"):
            continue
        name = f[:-4]
        t1 = time.perf_counter()
        rows = [[_plain(v) for v in row] for row in progs[name](frames)]
        seconds[name] = time.perf_counter() - t1
        with open(os.path.join(out_dir, name + ".json"), "w") as out:
            json.dump({"config": config_name, "query": name,
                       "made_by": "benchmark/reference/make_expected.py",
                       "rows": rows}, out, indent=1)
            out.write("\n")
        log(f"{config_name} {name}: {len(rows)} rows, {seconds[name]:.1f} s")
    return {"frames_s": frames_s, "query_s": seconds,
            "wall_s": time.perf_counter() - t0,
            "peak_rss_gib": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 2 ** 20}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the benchmark directory (default: this one)")
    args = ap.parse_args(argv)
    print(json.dumps(make(args.config, args.root)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
