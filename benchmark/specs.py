"""Find a cell's files by name.  No JAX, no program code.

``workloads/<cell>.json`` names the configuration, the traffic mix and
the chips; ``configs/<config>.json`` names the generator, the scale, the
resident tables and the layouts; ``traffic/<mix>.json`` holds the mix's
ordered query list and its parameters (a configuration and a mix make
one cell, so two cells of a configuration differ in their mix); the
query text and its sidecar are ``queries/<dir>/<query>.sql`` and
``.json``; the stored reference answer is
``expected/<config>/<query>.json``.  A later PR adds a cell, a
configuration, a mix or a query by adding files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.abspath(__file__))


def module_from_file(path: str):
    """The Python file at ``path`` as a module of its own.  Generators,
    per-layer readers and reference programs are found as files, so a
    later PR adds one without editing a package."""
    name = "benchmark_file_" + os.path.basename(path)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(root: str, *parts: str):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Query:
    name: str
    sql: str  # stripped; the traffic generator appends the padding
    ordered: bool
    reads: Dict[str, List[str]]  # table -> columns the query touches
    expected: List[tuple]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    layout: dict  # the configuration's layout for this chip count
    queries: List[Query]


def load_query(root: str, config: dict, name: str) -> Query:
    qdir = config["queries"]
    with open(os.path.join(root, "queries", qdir, name + ".sql")) as f:
        sql = f.read().strip()
    side = read_json(root, "queries", qdir, name + ".json")
    expected = read_json(root, "expected", config["name"], name + ".json")
    return Query(name, sql, bool(side["ordered"]), side["reads"],
                 [tuple(r) for r in expected["rows"]])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell with everything it names resolved; raises (KeyError,
    FileNotFoundError) where a file or a key is missing."""
    cell = read_json(root, "workloads", name + ".json")
    config = read_json(root, "configs", cell["config"] + ".json")
    traffic = read_json(root, "traffic", cell["traffic"] + ".json")
    layout = config["layouts"][str(cell["chips"])]
    queries = [load_query(root, config, q) for q in traffic["queries"]]
    for q in queries:
        for table, columns in q.reads.items():
            missing = [c for c in columns if c not in config["tables"][table]]
            if missing:
                raise KeyError(f"{q.name} reads {table}.{missing}, which "
                               f"{config['name']} does not keep resident")
    return Cell(cell["name"], int(cell["chips"]), config, traffic, layout,
                queries)


def cell_names(root: str = ROOT) -> List[str]:
    return sorted(f[:-len(".json")]
                  for f in os.listdir(os.path.join(root, "workloads"))
                  if f.endswith(".json"))
