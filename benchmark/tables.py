"""Make a configuration's tables resident on the device.

``MemoryConnector.load_from`` generates every column of every split,
uploads it, pulls it back to pad it to the capacity ladder and uploads
it again (PERF.md, PR 21, bottleneck 4).  Here each split's wanted
columns are padded on the host to their ladder capacity
(``exec.local.bucket_capacity``) and uploaded once, and the pages go to
``MemoryConnector.create_table`` with the generator's domains, primary
key, sort order and bucketing exactly as ``load_from`` passes them, so
the resident tables are the ones ``load_from`` would have made.

The padded host columns are kept as ``.npy`` files under
``benchmark/.cache/columns/`` (git-ignored), keyed by generator, scale
and split size: the first run of a configuration in a checkout writes
them, later runs map them and only upload.  Generated data is a pure
function of the key, so a cached column is the generated column.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark import specs

CACHE_VERSION = 1


def generator_for(config: dict, root: str = specs.ROOT):
    """The connector of the generator the configuration names, from
    ``generators/<name>.py``."""
    return specs.module_from_file(os.path.join(
        root, "generators", config["generator"] + ".py")).connector(config)


def cache_dir(config: dict, root: str = specs.ROOT) -> str:
    key = "{}-sf{:g}-split{}-v{}".format(
        config["generator"], float(config["scale_factor"]),
        int(config["split_rows"]), CACHE_VERSION)
    return os.path.join(root, ".cache", "columns", key)


def _host_table(conn, table: str, columns: List[str], directory: str,
                log) -> Tuple[List[int], List[Dict[str, np.ndarray]]]:
    """Per split: the row count, and column -> array padded with zeros
    to the split's ladder capacity.  From the cache where the table's
    marker is there, else generated and written."""
    from presto_tpu.exec.local import bucket_capacity

    marker = os.path.join(directory, table + ".json")
    if os.path.exists(marker):
        with open(marker) as f:
            meta = json.load(f)
        if meta["columns"] == columns:
            return meta["rows"], [
                {c: np.load(os.path.join(directory, f"{table}.{s}.{c}.npy"),
                            mmap_mode="r") for c in columns}
                for s in range(len(meta["rows"]))]
    os.makedirs(directory, exist_ok=True)
    types = dict(conn.schema(table))
    rows, splits = [], []
    for s in range(conn.num_splits(table)):
        data = conn.generate_split(table, s)
        n = len(data[columns[0]])
        cap = bucket_capacity(max(n, 1))
        padded = {}
        for c in columns:
            out = np.zeros((cap,) + types[c].value_shape,
                           dtype=types[c].np_dtype)
            out[:n] = data[c]
            padded[c] = out
            path = os.path.join(directory, f"{table}.{s}.{c}.npy")
            np.save(path + ".tmp.npy", out)
            os.replace(path + ".tmp.npy", path)
        del data
        rows.append(n)
        splits.append(padded)
        log(f"  generated {table} split {s}: {n} rows, capacity {cap}")
    with open(marker + ".tmp", "w") as f:
        json.dump({"columns": columns, "rows": rows}, f)
    os.replace(marker + ".tmp", marker)
    return rows, splits


def load(config: dict, root: str = specs.ROOT, log=lambda *_: None):
    """(memory connector, rows per table, seconds by phase).  The tables
    and columns are the configuration's, the same in every cell."""
    import jax.numpy as jnp

    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.page import Block, Page

    conn = generator_for(config, root)
    directory = cache_dir(config, root)
    mem = MemoryConnector()
    row_counts, host_s, upload_s = {}, 0.0, 0.0
    for table, wanted in config["tables"].items():
        schema = [(c, t) for c, t in conn.schema(table) if c in wanted]
        columns = [c for c, _ in schema]
        t0 = time.perf_counter()
        rows, splits = _host_table(conn, table, columns, directory, log)
        t1 = time.perf_counter()
        pages = []
        for n, cols in zip(rows, splits):
            cap = len(cols[columns[0]])
            live = np.zeros(cap, dtype=np.bool_)
            live[:n] = True
            pages.append(Page(
                tuple(Block(jnp.asarray(cols[c]), jnp.asarray(live), t,
                            conn.dictionary_for(table, c))
                      for c, t in schema),
                jnp.asarray(live)))
        for p in pages:  # the upload is asynchronous: wait for it
            p.row_mask.block_until_ready()
            for b in p.blocks:
                b.data.block_until_ready()
        del splits

        def kept(cols_):  # as load_from: drop metadata over pruned columns
            return cols_ is not None and all(c in columns for c in cols_)

        pk = conn.primary_key(table)
        so = conn.sort_order(table)
        bk = conn.bucketing(table)
        mem.create_table(
            table, schema, pages,
            {c: conn.column_domain(table, c) for c in columns},
            pk if kept(pk) else None,
            sort_order=so if kept(so) else None,
            bucketing=bk if bk is not None and kept(bk[0]) else None)
        row_counts[table] = int(sum(rows))
        host_s += t1 - t0
        upload_s += time.perf_counter() - t1
        log(f"  {table}: {row_counts[table]} rows in {len(rows)} splits")
    return mem, row_counts, {"host_s": host_s, "upload_s": upload_s}
