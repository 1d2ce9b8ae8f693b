"""Memory accounting against the device's HBM budget.

Reference analog: the hierarchical memory system —
``presto-memory-context`` (AggregatedMemoryContext/LocalMemoryContext),
``memory/MemoryPool.java:43`` (tagged reservations, listeners) and the
per-query limit enforcement of ``memory/QueryContext.java``.  The
reference tracks JVM heap bytes and kills/spills on pressure; here the
scarce resource is HBM, and the accountable objects are materialized
device intermediates (join builds, aggregation accumulators,
concatenated pages).  Exceeding the query limit raises
ExceededMemoryLimitError — the executor's capacity-retry machinery and
(future) host-offload chunking are the spill analogs.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from presto_tpu.sync import named_lock


class QueryKilledError(Exception):
    """Raised at the next reservation of a query the cluster memory
    manager killed — the execution thread's interruption point."""


class ExceededMemoryLimitError(Exception):
    def __init__(self, tag: str, requested: int, reserved: int, limit: int):
        super().__init__(
            f"query exceeded memory limit: {tag} requested {requested} bytes, "
            f"{reserved} reserved, limit {limit}"
        )
        self.tag = tag
        self.requested = requested
        self.reserved = reserved
        self.limit = limit


def page_bytes(page) -> int:
    """Accountable HBM footprint of a Page."""
    total = 0
    for b in page.blocks:
        total += b.data.size * b.data.dtype.itemsize
        total += b.valid.size  # bool byte each
    total += page.row_mask.size
    return total


class MemoryPool:
    """Tagged byte reservations with a hard limit (MemoryPool.java
    semantics minus the GENERAL/RESERVED two-pool OOM dance — a single
    chip has one HBM)."""

    def __init__(self, limit_bytes: int):
        self.limit = int(limit_bytes)
        self._lock = named_lock("memory.MemoryPool._lock")
        self._tagged: Dict[str, int] = {}
        self.reserved = 0
        self.peak = 0
        self._killed: set = set()

    def reserve(self, tag: str, nbytes: int, enforce: bool = True) -> None:
        """``enforce=False`` counts the bytes (peak/attribution) without
        failing on over-limit — for transient streaming state that
        cannot be spilled or retried (in-flight scan pages), bounded by
        split capacity rather than by the pool."""
        with self._lock:
            qid = tag.split("/", 1)[0]
            if qid in self._killed:
                raise QueryKilledError(f"query {qid} killed by the memory manager")
            if enforce and self.reserved + nbytes > self.limit:
                raise ExceededMemoryLimitError(tag, nbytes, self.reserved, self.limit)
            self._tagged[tag] = self._tagged.get(tag, 0) + nbytes
            self.reserved += nbytes
            self.peak = max(self.peak, self.reserved)

    def kill_query(self, query_id: str) -> int:
        """Free a query's reservations immediately and fail its future
        reserves (ClusterMemoryManager's actual relief mechanism — the
        execution thread dies at its next reservation)."""
        freed = 0
        with self._lock:
            self._killed.add(query_id)
            for tag in [t for t in self._tagged if t.split("/", 1)[0] == query_id]:
                freed += self._tagged.pop(tag)
            self.reserved -= freed
        # abort the query's streaming-exchange buffers too: a producer
        # thread blocked in enqueue (backpressure) never reaches its
        # next pool reservation, so without this it would leak
        try:
            from presto_tpu.parallel.streams import abort_query

            abort_query(query_id)
        except Exception:
            pass  # kill must still free memory if streams are torn down
        return freed

    def free(self, tag: str) -> None:
        with self._lock:
            n = self._tagged.pop(tag, 0)
            self.reserved -= n

    def free_all(self) -> None:
        with self._lock:
            self._tagged.clear()
            self.reserved = 0

    def tags(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._tagged)


class QueryMemoryContext:
    """Per-query view over a pool (QueryContext analog): unique tags
    per allocation site, freed together at query end.  Tracks its own
    reserved/peak so QueryStats can report per-query peak bytes, and
    per-SITE current/peak bytes (site = the ``what`` string, which for
    operator reservations embeds the plan-node id) so EXPLAIN ANALYZE
    can print per-operator peak memory from the tagged reservations.

    Thread-safe: the morsel split scheduler (exec/tasks.py) reserves
    and frees per-split tags from producer/worker threads while the
    consumer thread charges breaker state, so the context is SHARED
    per query rather than confined to one thread — a lock keeps the
    reserved/peak/site books consistent (the pool has its own lock;
    this one covers the query-local accounting)."""

    def __init__(self, pool: MemoryPool, query_id: str = "q"):
        self.pool = pool
        self.query_id = query_id
        self._lock = named_lock("memory.QueryMemoryContext._lock")
        self._seq = 0
        self.reserved = 0
        self.peak = 0
        self._tag_site: Dict[str, tuple] = {}  # tag -> (site, nbytes)
        self._site_current: Dict[str, int] = {}
        self.site_peak: Dict[str, int] = {}
        # per-query resource timeline, captured at construction on the
        # query thread: reserve/free also run on split-scheduler worker
        # threads, where the recording thread-local is not inherited
        from presto_tpu.obs.timeseries import current_timeline

        self._timeline = current_timeline()

    def _record_reserved(self, reserved_now: int) -> None:
        tl = self._timeline
        if tl is not None:
            tl.record("memory.reserved_bytes", float(reserved_now))

    def reserve(self, what: str, nbytes: int, enforce: bool = True) -> str:
        with self._lock:
            self._seq += 1
            tag = f"{self.query_id}/{what}#{self._seq}"
        # pool reservation outside the context lock: the pool enforces
        # its own limit under its own lock, and a kill/limit error must
        # not leave this context locked
        self.pool.reserve(tag, nbytes, enforce=enforce)
        with self._lock:
            self.reserved += nbytes
            self.peak = max(self.peak, self.reserved)
            reserved_now = self.reserved
            self._tag_site[tag] = (what, nbytes)
            cur = self._site_current.get(what, 0) + nbytes
            self._site_current[what] = cur
            if cur > self.site_peak.get(what, 0):
                self.site_peak[what] = cur
        self._record_reserved(reserved_now)
        return tag

    def reserve_page(self, what: str, page) -> str:
        return self.reserve(what, page_bytes(page))

    def free(self, tag: str) -> None:
        n = self.pool.tags().get(tag, 0)
        self.pool.free(tag)
        with self._lock:
            self.reserved -= n
            reserved_now = self.reserved
            entry = self._tag_site.pop(tag, None)
            if entry is not None:
                site, nbytes = entry
                self._site_current[site] = (
                    self._site_current.get(site, 0) - nbytes)
        self._record_reserved(reserved_now)

    def headroom(self) -> int:
        """Pool bytes still available — the split scheduler's
        backpressure probe (dispatch defers while a further in-flight
        split would not fit)."""
        return self.pool.limit - self.pool.reserved

    def release_all(self) -> None:
        for tag in list(self.pool.tags()):
            if tag.startswith(self.query_id + "/"):
                self.pool.free(tag)
        with self._lock:
            self.reserved = 0
            self._tag_site.clear()
            self._site_current.clear()


# ---------------------------------------------------------------------------
# default (always-on) pool
# ---------------------------------------------------------------------------

_DEFAULT_POOL: Optional[MemoryPool] = None
_DEFAULT_LOCK = named_lock("memory._DEFAULT_LOCK")


def detected_memory_limit() -> int:
    """Accountable-memory budget for the default pool: 90% of the
    device's reported HBM on an accelerator, half of host RAM on the
    CPU backend.  PRESTO_TPU_MEMORY_LIMIT_BYTES overrides (testing and
    deployments with reserved headroom).  An accelerator that reports
    no limit is an error: sizing its pool from host RAM would admit
    work the device cannot hold."""
    import os

    env = os.environ.get("PRESTO_TPU_MEMORY_LIMIT_BYTES")
    if env:
        return int(env)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "cpu":
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if not limit:
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                f"bytes_limit; set PRESTO_TPU_MEMORY_LIMIT_BYTES")
        return int(limit * 0.9)
    with open("/proc/meminfo") as f:
        kb = int(next(ln for ln in f if ln.startswith("MemTotal")).split()[1])
    return kb * 1024 // 2


def default_memory_pool() -> MemoryPool:
    """Process-wide pool shared by every runner that doesn't bring its
    own — the single-HBM worker pool (memory/LocalMemoryManager.java
    role).  Accounting is unconditional: an untracked path that works
    at SF0.01 OOMs silently at SF100."""
    global _DEFAULT_POOL
    with _DEFAULT_LOCK:
        if _DEFAULT_POOL is None:
            _DEFAULT_POOL = MemoryPool(detected_memory_limit())
            wire_pool_gauges(_DEFAULT_POOL)
        return _DEFAULT_POOL


def wire_pool_gauges(pool: MemoryPool) -> None:
    """Attach the ``memory.pool_*`` gauges (pre-registered in the
    obs catalog) to ``pool``.  Gauges sample through callbacks at
    snapshot/scrape time, so they always read the live pool state.
    Process semantics: ONE accountable pool per process (the default
    pool, or a server's injected one) — the most recently wired pool
    wins, which lets tests swap pools freely."""
    from presto_tpu.obs import METRICS

    METRICS.gauge("memory.pool_reserved_bytes").set_fn(
        lambda: pool.reserved)
    METRICS.gauge("memory.pool_peak_bytes").set_fn(lambda: pool.peak)
    METRICS.gauge("memory.pool_limit_bytes").set_fn(lambda: pool.limit)
    METRICS.gauge("memory.pool_queries").set_fn(
        lambda: len({t.split("/", 1)[0] for t in pool.tags()}))
