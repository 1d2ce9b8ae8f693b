"""File-based configuration.

Reference analog: airlift ``@Config`` bean binding from
``etc/config.properties`` (server/PrestoServer.java bootstraps from the
etc/ directory: config.properties, node.properties, plus per-catalog
``etc/catalog/*.properties``).  Java-properties syntax: ``key=value``
lines, ``#``/``!`` comments, no sections.

Recognized keys (the engine's subset of the reference's config space):
  coordinator                 true/false (role selection)
  http-server.http.port       REST port
  node.id                     stable node identifier
  query.max-memory-per-node   bytes for the local MemoryPool
  query.validate-plans        run the static plan/IR validator on every
                              bound plan (docs/static-analysis.md)
  query.validate-rewrites     gate every optimizer rule application
                              with the rewrite-soundness checker
                              (docs/static-analysis.md)
  query.trace-dir             write one Chrome-trace JSON per query
                              (docs/observability.md; enables tracing)
  query.log-path              JSONL query log (one line per completed
                              query via the EventListener sink)
  query.task-concurrency      splits in flight per scan pipeline
                              (morsel split scheduler; docs/tuning.md)
  query.task-prefetch         host pages prepared ahead of the split
                              worker pool (double-buffering depth)
  query.max-execution-time    duration (e.g. ``600s``, ``10m``) a query
                              may RUN before the coordinator kills it
                              (EXCEEDED_TIME_LIMIT; default 0 = no
                              deadline; docs/fault-tolerance.md)
  query.max-queued-time       duration a query may wait for resource-
                              group admission before failing
  coordinator.worker-uris     comma-separated worker base URIs the
                              coordinator heartbeats, polls and
                              schedules (failure detector, cluster
                              memory manager, system tables)
  query.result-cache-enabled  serve repeated read-only queries from the
                              structural result cache (docs/serving.md)
  query.result-cache-bytes    byte budget for that cache (0 = default)
  query.subplan-cache-enabled reuse warm stage intermediates at
                              exchange boundaries (docs/serving.md)
  query.admission-memory-fraction
                              dispatch only while pool reserved +
                              projected bytes <= fraction * limit
  query.admission-reserve-bytes
                              memory projection for statements with no
                              observed peak history
  task.buffer-bytes           worker output-buffer cap
  session.<property>          default for any system session property

Catalog files (``etc/catalog/<name>.properties``) declare
``connector.name=<tpch|tpcds|memory|blackhole|...>`` plus
connector-specific keys (e.g. ``tpch.scale-factor=1.0``), mirroring the
reference's per-catalog property files.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple


def parse_properties(text: str) -> Dict[str, str]:
    """Java-properties subset: key=value, # or ! comments, blank lines."""
    out: Dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or line.startswith("!"):
            continue
        if "=" not in line:
            raise ValueError(f"malformed property line: {raw!r}")
        k, v = line.split("=", 1)
        out[k.strip()] = v.strip()
    return out


def load_properties(path: str) -> Dict[str, str]:
    with open(path) as f:
        return parse_properties(f.read())


def parse_duration(text: str, default: float = 0.0) -> float:
    """airlift ``Duration`` subset -> seconds: ``600``/``600s``,
    ``500ms``, ``10m``, ``2h``, ``1d``.  Empty/None/unparseable ->
    ``default`` (never raises: this runs on the coordinator's
    query-execution path, where a garbage config value must degrade
    to the default, not leak a resource-group slot — session values
    are additionally validated at SET time, session.py).  ``0`` (any
    unit) means disabled by the callers' convention."""
    if text is None:
        return default
    s = str(text).strip().lower()
    if not s:
        return default
    try:
        for suffix, scale in (("ms", 1e-3), ("s", 1.0), ("m", 60.0),
                              ("h", 3600.0), ("d", 86400.0)):
            if s.endswith(suffix):
                return float(s[: -len(suffix)]) * scale
        return float(s)
    except ValueError:
        return default


class EngineConfig:
    """Parsed etc/ directory (PrestoServer bootstrap analog)."""

    def __init__(self, props: Optional[Dict[str, str]] = None,
                 catalogs: Optional[Dict[str, Dict[str, str]]] = None):
        self.props = dict(props or {})
        self.catalogs = dict(catalogs or {})

    # -- typed accessors ----------------------------------------------------
    def bool(self, key: str, default: bool = False) -> bool:
        v = self.props.get(key)
        return default if v is None else v.lower() in ("true", "1", "yes")

    def int(self, key: str, default: int = 0) -> int:
        v = self.props.get(key)
        return default if v is None else int(v)

    def str(self, key: str, default: str = "") -> str:
        return self.props.get(key, default)

    def session_defaults(self) -> Dict[str, str]:
        """``session.<name>`` keys become session-property defaults."""
        return {
            k[len("session."):]: v
            for k, v in self.props.items()
            if k.startswith("session.")
        }

    def max_execution_time(self, default: float = 0.0) -> float:
        """``query.max-execution-time`` in seconds.  Default 0 = no
        deadline: a kill policy must be OPTED INTO — an unchanged
        config keeps the legacy behavior where long queries run to
        completion (the old 600s was only a long-poll bound, and
        silently turning it into a kill would fail every >10min query
        on upgrade)."""
        return parse_duration(self.props.get("query.max-execution-time"),
                              default)

    def max_queued_time(self, default: float = 600.0) -> float:
        """``query.max-queued-time`` in seconds: the resource-group
        admission wait bound (expiry = a FAILED statement, not a
        hang)."""
        return parse_duration(self.props.get("query.max-queued-time"),
                              default)

    def query_log_path(self) -> Optional[str]:
        """Path for the JSONL query log (``query.log-path``); None
        disables the sink."""
        v = self.props.get("query.log-path")
        return v if v and v.strip() not in ("0", "false") else None

    # -- loading ------------------------------------------------------------
    @classmethod
    def from_etc(cls, etc_dir: str) -> "EngineConfig":
        props = {}
        cfg = os.path.join(etc_dir, "config.properties")
        if os.path.exists(cfg):
            props.update(load_properties(cfg))
        node = os.path.join(etc_dir, "node.properties")
        if os.path.exists(node):
            props.update(load_properties(node))
        catalogs = {}
        catdir = os.path.join(etc_dir, "catalog")
        if os.path.isdir(catdir):
            for fn in sorted(os.listdir(catdir)):
                if fn.endswith(".properties"):
                    catalogs[fn[:-len(".properties")]] = load_properties(
                        os.path.join(catdir, fn))
        return cls(props, catalogs)

    # -- materialization ----------------------------------------------------
    def build_catalog(self, plugin_manager=None):
        """Instantiate connectors from the catalog property files
        (PluginManager + ConnectorFactory analog, keyed by
        ``connector.name``).  Unknown kinds resolve through the plugin
        manager (``plugin.dir`` in config.properties loads one)."""
        from presto_tpu.catalog import Catalog

        if plugin_manager is None and self.props.get("plugin.dir"):
            from presto_tpu.plugin import PluginManager

            plugin_manager = PluginManager()
            plugin_manager.load_directory(self.props["plugin.dir"])
        catalog = Catalog()
        for name, props in self.catalogs.items():
            kind = props.get("connector.name")
            if kind in _BUILTIN_CONNECTORS:
                conn = _make_connector(kind, props)
            elif plugin_manager is not None and kind in plugin_manager.connector_factories:
                conn = plugin_manager.make_connector(kind, props)
            else:
                raise ValueError(f"unknown connector.name: {kind!r}")
            catalog.register(name, conn)
        return catalog

    def build_session(self):
        from presto_tpu.session import Session

        props = self.session_defaults()
        # query.validate-plans: always-on static plan validation (the
        # dotted key mirrors the reference's config namespace; it is
        # sugar for session.validate_plans)
        v = self.props.get("query.validate-plans")
        if v is not None and "validate_plans" not in props:
            props["validate_plans"] = v
        # query.validate-rewrites: per-rewrite soundness gating in the
        # iterative optimizer (same sugar shape as validate-plans)
        v = self.props.get("query.validate-rewrites")
        if v is not None and "validate_rewrites" not in props:
            props["validate_rewrites"] = v
        # query.validate-kernels: expression-tier kernel-soundness
        # gating (same sugar shape as validate-plans)
        v = self.props.get("query.validate-kernels")
        if v is not None and "validate_kernels" not in props:
            props["validate_kernels"] = v
        # query.task-concurrency / query.task-prefetch: morsel split
        # scheduler defaults (dotted keys mirror the reference's
        # task.concurrency config; sugar for session.task_*)
        v = self.props.get("query.task-concurrency")
        if v is not None and "task_concurrency" not in props:
            props["task_concurrency"] = v
        v = self.props.get("query.task-prefetch")
        if v is not None and "task_prefetch" not in props:
            props["task_prefetch"] = v
        # query.result-cache-enabled / query.subplan-cache-enabled:
        # serving-tier cache defaults (docs/serving.md; sugar for
        # session.result_cache_enabled / session.subplan_cache_enabled)
        v = self.props.get("query.result-cache-enabled")
        if v is not None and "result_cache_enabled" not in props:
            props["result_cache_enabled"] = v
        v = self.props.get("query.subplan-cache-enabled")
        if v is not None and "subplan_cache_enabled" not in props:
            props["subplan_cache_enabled"] = v
        return Session(properties=props)

    # -- serving tier (admission + caches; docs/serving.md) -----------------
    def result_cache_bytes(self, default: int = 0) -> int:
        """``query.result-cache-bytes``: byte budget for the structural
        result cache (0 = the process default, 64 MiB or
        PRESTO_TPU_RESULT_CACHE_BYTES)."""
        return self.int("query.result-cache-bytes", default)

    def admission_memory_fraction(self, default: float = 0.9) -> float:
        """``query.admission-memory-fraction``: a query dispatches only
        while pool reserved + its projected bytes stay under this
        fraction of the pool limit (<= 0 disables the memory gate)."""
        v = self.props.get("query.admission-memory-fraction")
        if v is None:
            return default
        try:
            return float(v)
        except ValueError:
            return default

    def admission_reserve_bytes(self, default: int = 0) -> int:
        """``query.admission-reserve-bytes``: the memory projection for
        a statement with no observed history (0 = admit on the
        fraction gate alone)."""
        return self.int("query.admission-reserve-bytes", default)


_BUILTIN_CONNECTORS = ("tpch", "tpcds", "memory", "blackhole", "jdbc",
                       "localfile", "pcf", "rgf", "warehouse", "shardstore",
                       "remote", "stream", "kv", "metrics", "http")


def _make_connector(kind: Optional[str], props: Dict[str, str]):
    if kind == "tpch":
        from presto_tpu.connectors.tpch import Tpch

        return Tpch(
            sf=float(props.get("tpch.scale-factor", "0.01")),
            split_rows=int(props.get("tpch.split-rows", str(1 << 20))),
        )
    if kind == "tpcds":
        from presto_tpu.connectors.tpcds import Tpcds

        return Tpcds(sf=float(props.get("tpcds.scale-factor", "0.01")))
    if kind == "memory":
        from presto_tpu.connectors.memory import MemoryConnector

        return MemoryConnector()
    if kind == "blackhole":
        from presto_tpu.connectors.blackhole import BlackholeConnector

        return BlackholeConnector()
    if kind == "jdbc":
        from presto_tpu.connectors.jdbc import JdbcConnector

        return JdbcConnector.sqlite(props["jdbc.path"])
    if kind == "localfile":
        import json as _json

        from presto_tpu.connectors.localfile import LocalFileConnector

        conn = LocalFileConnector()
        with open(props["localfile.catalog"]) as f:
            for t in _json.load(f):  # [{name, path, format, schema}, ...]
                conn.add_table(t["name"], t["path"], t["format"],
                               [tuple(cs) for cs in t["schema"]])
        return conn
    if kind == "pcf":
        from presto_tpu.storage.pcf import PcfConnector

        return PcfConnector(props["pcf.root"])
    if kind == "rgf":
        from presto_tpu.storage.rgf import RgfConnector

        return RgfConnector(
            props["rgf.root"],
            split_bytes=int(props.get("rgf.split-bytes", str(1 << 22))))
    if kind == "warehouse":
        from presto_tpu.storage.warehouse import WarehouseConnector

        return WarehouseConnector(props["warehouse.root"])
    if kind == "shardstore":
        from presto_tpu.storage.shardstore import ShardStoreConnector

        nodes = [n.strip() for n in
                 props.get("shardstore.nodes", "node0").split(",")]
        return ShardStoreConnector(
            props["shardstore.root"], nodes=nodes,
            max_shard_rows=int(props.get("shardstore.max-shard-rows",
                                         str(1 << 20))),
            backup_root=props.get("shardstore.backup-root"))
    if kind == "remote":
        from presto_tpu.connectors.remote import RemoteConnector

        return RemoteConnector(props["remote.uri"])
    if kind == "stream":
        import json as _json

        from presto_tpu.connectors.stream import LogBroker, StreamConnector

        with open(props["stream.table-descriptions"]) as f:
            desc = _json.load(f)
        return StreamConnector(LogBroker(props["stream.root"]), desc)
    if kind == "kv":
        import json as _json

        from presto_tpu.connectors.stream import KvConnector

        with open(props["kv.table-descriptions"]) as f:
            desc = _json.load(f)
        return KvConnector(props["kv.path"], desc)
    if kind == "metrics":
        from presto_tpu.connectors.metrics import MetricsConnector

        return MetricsConnector()
    if kind == "http":
        from presto_tpu.connectors.http import HttpConnector

        return HttpConnector(catalog_uri=props["http.catalog-uri"])
    raise ValueError(f"unknown connector.name: {kind!r}")
