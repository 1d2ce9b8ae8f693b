"""In-memory (HBM-resident) table connector.

Reference analog: ``presto-memory`` (worker-RAM tables,
``presto-memory/src/main/java/com/facebook/presto/plugin/memory/``).
Tables are lists of device-resident Pages; loading from another
connector is the CTAS path.  Used by benchmarks to measure pure device
execution without per-run host data generation.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from presto_tpu.page import Page
from presto_tpu.types import Type


class MemoryConnector:
    def __init__(self):
        self._tables: Dict[str, List[Page]] = {}
        self._schemas: Dict[str, List[Tuple[str, Type]]] = {}
        self._domains: Dict[str, Dict[str, Optional[Tuple[int, int]]]] = {}
        self._pks: Dict[str, Optional[List[str]]] = {}
        self._sort: Dict[str, Optional[List[str]]] = {}
        self._bucketing: Dict[str, Optional[tuple]] = {}
        self._dicts: Dict[str, Dict[str, object]] = {}
        # monotonically increasing per-table data version, bumped by
        # EVERY mutation (CTAS/INSERT/DELETE-rewrite/DDL) — the serving
        # tier's cache-invalidation token (serving/cache.py); one shared
        # counter so a drop+recreate can never repeat an old number,
        # paired with a per-INSTANCE token so two connectors holding
        # same-named, same-shaped tables with different data can never
        # alias each other's cache entries
        import uuid as _uuid

        self._instance_id = _uuid.uuid4().hex[:12]
        self._versions: Dict[str, int] = {}
        self._version_seq = 0

    def _bump_version(self, name: str) -> None:
        self._version_seq += 1
        self._versions[name] = self._version_seq

    def table_version(self, name: str):
        """Current data version: (instance token, counter); the counter
        is 0 until the first write through this connector instance."""
        return (self._instance_id, self._versions.get(name, 0))

    # -- loading ------------------------------------------------------------
    def create_table(
        self,
        name: str,
        schema: Sequence[Tuple[str, Type]],
        pages: Sequence[Page],
        domains: Optional[Dict[str, Tuple[int, int]]] = None,
        primary_key: Optional[List[str]] = None,
        sort_order: Optional[List[str]] = None,
        bucketing: Optional[tuple] = None,
    ) -> None:
        self._tables[name] = [_to_device(p) for p in pages]
        self._schemas[name] = list(schema)
        self._domains[name] = dict(domains or {})
        self._pks[name] = primary_key
        self._sort[name] = list(sort_order) if sort_order else None
        self._bucketing[name] = bucketing
        self._dicts[name] = {}
        for page in pages[:1]:
            for (col, t), b in zip(schema, page.blocks):
                if t.is_string:
                    self._dicts[name][col] = b.dictionary
        self._bump_version(name)

    def append_pages(self, name: str, pages: Sequence[Page]) -> None:
        pages = [_to_device(p) for p in pages]
        # the domain first: it is true of every row that can be counted
        # (docs/static-analysis.md, what a connector owes)
        self._widen_domains(name, pages)
        self._tables[name].extend(pages)
        self._bump_version(name)

    def _widen_domains(self, name: str, pages: Sequence[Page]) -> None:
        """A declared domain is a licence: the planner packs keys by
        it and the executor drops an arithmetic guard or sums in one
        lane where it proves that safe (docs/static-analysis.md).  So
        an append keeps it true: each declared column's domain widens
        to the new pages' observed min and max (live, non-NULL rows),
        and a column that cannot be observed as one scalar lane loses
        its domain."""
        import numpy as np

        doms = self._domains.get(name)
        if not doms:
            return
        for i, (col, t) in enumerate(self._schemas[name]):
            dom = doms.get(col)
            if dom is None:
                continue
            if t.value_shape != () or t.is_raw_string:
                doms[col] = None
                continue
            lo, hi = dom
            for p in pages:
                b = p.blocks[i]
                live = np.asarray(p.row_mask) & np.asarray(b.valid)
                if live.any():
                    vals = np.asarray(b.data)[live]
                    lo = min(lo, vals.min().tolist())
                    hi = max(hi, vals.max().tolist())
            doms[col] = (lo, hi)

    def drop_table(self, name: str) -> None:
        for d in (self._tables, self._schemas, self._domains, self._pks,
                  self._sort, self._bucketing, self._dicts):
            d.pop(name, None)
        self._bump_version(name)

    def add_column(self, name: str, column: str, ctype: Type) -> None:
        """ALTER TABLE ADD COLUMN: existing rows read NULL in the new
        column (MemoryMetadata.addColumn analog — the reference's
        memory connector rejects this; hive-style NULL backfill here)."""
        import jax.numpy as jnp

        from presto_tpu.page import Block, Dictionary

        if any(c == column for c, _ in self._schemas[name]):
            raise ValueError(f"column {column} already exists in {name}")
        self._schemas[name] = list(self._schemas[name]) + [(column, ctype)]
        # dictionary-coded string columns get an empty dictionary so
        # downstream decode paths stay total (raw_varchar/varbinary are
        # value-carrying and take none)
        dic = (Dictionary([])
               if ctype.is_string and not ctype.is_raw_string else None)
        if dic is not None:
            self._dicts.setdefault(name, {})[column] = dic
        new_pages = []
        for p in self._tables[name]:
            data = jnp.zeros((p.capacity,) + ctype.value_shape,
                             dtype=ctype.np_dtype)
            blk = Block(data, jnp.zeros((p.capacity,), dtype=jnp.bool_),
                        ctype, dic)
            new_pages.append(Page(tuple(p.blocks) + (blk,), p.row_mask))
        self._tables[name] = new_pages
        self._bump_version(name)

    def drop_column(self, name: str, column: str) -> None:
        idxs = [i for i, (c, _) in enumerate(self._schemas[name])
                if c != column]
        if len(idxs) == len(self._schemas[name]):
            raise ValueError(f"column {column} not found in {name}")
        if not idxs:
            raise ValueError("cannot drop the only column")
        self._schemas[name] = [self._schemas[name][i] for i in idxs]
        self._tables[name] = [
            Page(tuple(p.blocks[i] for i in idxs), p.row_mask)
            for p in self._tables[name]
        ]
        self._domains.get(name, {}).pop(column, None)
        self._dicts.get(name, {}).pop(column, None)
        # planner metadata referencing the dropped column is void
        if self._pks.get(name) and column in self._pks[name]:
            self._pks[name] = None
        if self._sort.get(name) and column in self._sort[name]:
            self._sort[name] = None
        bk = self._bucketing.get(name)
        if bk is not None and column in bk[0]:
            self._bucketing[name] = None
        self._bump_version(name)

    def rename_table(self, name: str, new_name: str) -> None:
        if new_name in self._tables:
            raise ValueError(f"table {new_name} already exists")
        for d in (self._tables, self._schemas, self._domains, self._pks,
                  self._sort, self._bucketing, self._dicts):
            if name in d:
                d[new_name] = d.pop(name)
        self._bump_version(name)
        self._bump_version(new_name)

    def load_from(self, conn, table: str, name: Optional[str] = None,
                  columns: Optional[List[str]] = None) -> None:
        """Copy a table from another connector onto the device (CTAS).
        ``columns`` prunes to the listed columns."""
        name = name or table
        schema = conn.schema(table)
        keep = [i for i, (c, _) in enumerate(schema)
                if columns is None or c in columns]
        pages = []
        for s in range(conn.num_splits(table)):
            p = conn.page_for_split(table, s)
            pages.append(Page(tuple(p.blocks[i] for i in keep), p.row_mask))
        pruned_schema = [schema[i] for i in keep]
        domains = {}
        if hasattr(conn, "column_domain"):
            for c, _ in pruned_schema:
                domains[c] = conn.column_domain(table, c)
        pk = conn.primary_key(table) if hasattr(conn, "primary_key") else None
        if pk is not None and any(c not in [n for n, _ in pruned_schema] for c in pk):
            pk = None
        so = conn.sort_order(table) if hasattr(conn, "sort_order") else None
        if so is not None and any(c not in [n for n, _ in pruned_schema] for c in so):
            so = None
        bk = conn.bucketing(table) if hasattr(conn, "bucketing") else None
        if bk is not None and any(c not in [n for n, _ in pruned_schema] for c in bk[0]):
            bk = None
        self.create_table(name, pruned_schema, pages, domains, pk,
                          sort_order=so, bucketing=bk)

    # -- connector protocol -------------------------------------------------
    def table_names(self) -> List[str]:
        return list(self._tables.keys())

    def schema(self, table: str) -> List[Tuple[str, Type]]:
        return self._schemas[table]

    def num_splits(self, table: str) -> int:
        return len(self._tables[table])

    def page_for_split(self, table: str, split: int, capacity: Optional[int] = None) -> Page:
        """Stored pages keep their load-time ladder capacity, which a
        ragged last split makes smaller than its siblings'; a caller
        that stacks splits (the mesh tier's waves) names the one
        ``capacity`` it needs and gets dead-row padding up to it."""
        page = self._tables[table][split]
        if capacity is not None:
            from presto_tpu.exec.local import pad_page_to

            page = pad_page_to(page, capacity)
        return page

    def row_count(self, table: str) -> int:
        """One blocking read a page (bind time asks per statement)."""
        from presto_tpu.obs.reads import host_read

        return sum(int(host_read(p.num_rows(), "row_count"))
                   for p in self._tables[table])

    def column_domain(self, table: str, column: str) -> Optional[Tuple[int, int]]:
        return self._domains.get(table, {}).get(column)

    def primary_key(self, table: str) -> Optional[List[str]]:
        return self._pks.get(table)

    def sort_order(self, table: str) -> Optional[List[str]]:
        """Declared physical ordering of the stored pages (feeds the
        streaming-aggregation path; ConnectorMetadata local-properties
        analog)."""
        return self._sort.get(table)

    def bucketing(self, table: str) -> Optional[tuple]:
        """(bucket_columns, alignment_token, bucket_count): split index
        is the bucket id (ConnectorNodePartitioningProvider analog)."""
        return self._bucketing.get(table)

    def dictionary_for(self, table: str, column: str):
        return self._dicts.get(table, {}).get(column)

    def max_split_rows(self, table: str) -> int:
        return max(p.capacity for p in self._tables[table])

    # -- transactions --------------------------------------------------------
    # Reference: ConnectorMetadata transaction hooks driven by
    # transaction/TransactionManager.java.  Writes stage on the handle
    # and publish atomically at commit (read-committed; no
    # read-your-writes inside an open transaction).

    def begin_transaction(self):
        return _MemoryTx()

    def commit_transaction(self, tx: "_MemoryTx") -> None:
        for op, args in tx.ops:
            getattr(self, op)(*args)

    def rollback_transaction(self, tx: "_MemoryTx") -> None:
        tx.ops.clear()

    def stage(self, tx: "_MemoryTx", op: str, *args) -> None:
        """Record a write to apply at commit (op = method name)."""
        tx.ops.append((op, args))


class _MemoryTx:
    """Staged write list (ConnectorTransactionHandle analog)."""

    def __init__(self):
        self.ops: List[tuple] = []


def _to_device(page: Page):
    """Pin a page's arrays in HBM once at write time — compacted result
    pages arrive numpy-backed (page.compact_host), and storing them
    as-is would re-pay the host->device transfer on every later scan.
    Pages also pad to pow2 capacity HERE, once: scan-time padding
    (exec/local.pad_page_pow2) costs a ~50ms device concat per ragged
    page per execution, so resident tables pre-pay it at load."""
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.page import Block

    from presto_tpu.exec.local import bucket_capacity

    cap = page.capacity
    tgt = bucket_capacity(cap)
    if tgt > cap:
        def padded(a):
            a = np.asarray(a)
            out = np.zeros((tgt,) + a.shape[1:], dtype=a.dtype)
            out[:cap] = a
            return out

        page = Page(
            tuple(Block(padded(b.data), padded(b.valid), b.type,
                        b.dictionary) for b in page.blocks),
            padded(page.row_mask))
    if not any(isinstance(b.data, np.ndarray) for b in page.blocks):
        return page
    return Page(
        tuple(
            Block(jnp.asarray(b.data), jnp.asarray(b.valid), b.type, b.dictionary)
            for b in page.blocks
        ),
        jnp.asarray(page.row_mask),
    )
