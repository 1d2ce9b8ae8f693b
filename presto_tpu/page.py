"""Columnar device batches: Block and Page.

Reference analog: ``presto-spi/.../spi/Page.java:34`` (array of Blocks +
positionCount) and ``spi/block/Block.java:23``.  The reference's Blocks
are heap byte slices with per-position object access; here a Block is a
dense device array plus a validity bitmap so every operator is a
whole-array XLA computation.

TPU-first representational choices:

* **Static capacity.** XLA wants static shapes.  A Page's arrays all
  have length ``capacity`` (padded); the live rows are flagged by a
  boolean ``row_mask`` (the analog of Presto's SelectedPositions,
  operator/project/SelectedPositions.java, but kept as a mask instead of
  a position list so filters are free and nothing ever recompiles).
  Compaction happens only at exchange boundaries or host output.

* **Two masks.** ``Block.valid`` is SQL NULL-ness per value;
  ``Page.row_mask`` is row liveness after filters.  Operators must
  ignore rows where ``row_mask`` is False.

* **Dictionary blocks.** VARCHAR columns store int32 codes; the code ->
  string mapping is a host-side :class:`Dictionary` (reference:
  spi/block/DictionaryBlock.java).  String predicates evaluate once on
  the dictionary host-side, becoming a device boolean LUT gather.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.types import Type


class Dictionary:
    """Host-side immutable string dictionary for a VARCHAR column.

    Codes are indexes into ``values``.  Identity-hashed so it can ride
    in jit-static fields without content comparison.
    """

    __slots__ = ("values", "_index")

    def __init__(self, values: Sequence[str]):
        self.values = list(values)
        self._index: Optional[Dict[str, int]] = None

    def code_of(self, s: str) -> int:
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index.get(s, -1)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        arr = np.asarray(self.values, dtype=object)
        out = np.empty(codes.shape, dtype=object)
        in_range = (codes >= 0) & (codes < len(self.values))
        out[in_range] = arr[codes[in_range]]
        out[~in_range] = None
        return out

    def lut(self, predicate) -> np.ndarray:
        """Evaluate a python str->bool predicate over all unique values,
        returning a bool LUT indexable by code (device-gatherable)."""
        return np.asarray([bool(predicate(v)) for v in self.values], dtype=np.bool_)

    def __len__(self) -> int:
        return len(self.values)

    def __repr__(self) -> str:
        return f"Dictionary({len(self.values)} values)"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Block:
    """One column: dense device array + validity bitmap.

    ``data`` and ``valid`` have shape ``(capacity,)``.  ``type`` and
    ``dictionary`` are static (not traced).
    """

    data: jax.Array
    valid: jax.Array
    type: Type
    dictionary: Optional[Dictionary] = None

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.valid), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid = children
        type_, dictionary = aux
        return cls(data=data, valid=valid, type=type_, dictionary=dictionary)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        values: np.ndarray,
        type_: Type,
        valid: Optional[np.ndarray] = None,
        dictionary: Optional[Dictionary] = None,
        capacity: Optional[int] = None,
    ) -> "Block":
        n = len(values)
        cap = capacity if capacity is not None else n
        if type_.is_long_decimal and (
            not isinstance(values, np.ndarray) or values.ndim == 1
        ):
            # python ints (possibly > 2^63) -> base-10^18 (or, for
            # decimal(37..38), base-10^9) limbs
            from presto_tpu.ops.decimal128 import encode_py

            data = encode_py(list(values), cap,
                             limbs=type_.value_shape[0])
        elif type_.is_raw_string and not isinstance(values, np.ndarray):
            from presto_tpu.ops.rawstring import encode_strings

            width = type_.value_shape[0]
            data = np.zeros((cap, width), dtype=np.uint8)
            data[:n] = encode_strings(list(values), width)
        elif type_.is_array and (
            not isinstance(values, np.ndarray) or values.ndim == 1
        ):
            from presto_tpu.ops.container import encode_arrays

            data = encode_arrays(list(values), type_, cap)
        elif type_.is_map and (
            not isinstance(values, np.ndarray) or values.ndim == 1
        ):
            from presto_tpu.ops.container import encode_maps

            data = encode_maps(list(values), type_, cap)
        else:
            data = np.zeros((cap,) + type_.value_shape, dtype=type_.np_dtype)
            data[:n] = values
        v = np.zeros(cap, dtype=np.bool_)
        v[:n] = True if valid is None else valid
        return cls(jnp.asarray(data), jnp.asarray(v), type_, dictionary)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def __repr__(self) -> str:
        return f"Block({self.type}, capacity={self.capacity})"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    """A batch of rows: tuple of Blocks + row liveness mask.

    Reference: spi/Page.java.  ``positionCount`` becomes the dynamic
    ``num_rows()`` (popcount of row_mask); shapes stay static.
    """

    blocks: Tuple[Block, ...]
    row_mask: jax.Array  # bool (capacity,)

    def tree_flatten(self):
        return (self.blocks, self.row_mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        blocks, row_mask = children
        return cls(blocks=tuple(blocks), row_mask=row_mask)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_arrays(
        cls,
        columns: Sequence[np.ndarray],
        types: Sequence[Type],
        valids: Optional[Sequence[Optional[np.ndarray]]] = None,
        dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
        capacity: Optional[int] = None,
    ) -> "Page":
        n = len(columns[0]) if columns else 0
        cap = capacity if capacity is not None else max(n, 1)
        blocks = []
        for i, (col, t) in enumerate(zip(columns, types)):
            v = valids[i] if valids is not None else None
            d = dictionaries[i] if dictionaries is not None else None
            blocks.append(Block.from_numpy(col, t, valid=v, dictionary=d, capacity=cap))
        mask = np.zeros(cap, dtype=np.bool_)
        mask[:n] = True
        return cls(tuple(blocks), jnp.asarray(mask))

    @classmethod
    def empty(cls, types: Sequence[Type], capacity: int) -> "Page":
        blocks = tuple(
            Block(
                jnp.zeros((capacity,) + t.value_shape, dtype=t.np_dtype),
                jnp.zeros(capacity, dtype=jnp.bool_),
                t,
            )
            for t in types
        )
        return cls(blocks, jnp.zeros(capacity, dtype=jnp.bool_))

    # -- properties --------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.row_mask.shape[0]

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    @property
    def types(self) -> Tuple[Type, ...]:
        return tuple(b.type for b in self.blocks)

    def num_rows(self) -> jax.Array:
        return jnp.sum(self.row_mask.astype(jnp.int32))

    # -- host materialization ---------------------------------------------
    def device_get(self) -> "Page":
        """One batched device->host transfer of the whole page: every
        *separate* host read is a device round trip, so serial
        ``np.asarray`` per block is k+1 of them; ``jax.device_get`` of
        the pytree batches them.
        The returned Page holds numpy arrays (valid pytree leaves —
        they re-upload transparently if handed back to device code)."""
        datas, valids, mask = jax.device_get((
            tuple(b.data for b in self.blocks),
            tuple(b.valid for b in self.blocks),
            self.row_mask,
        ))
        return Page(
            tuple(
                Block(d, v, b.type, b.dictionary)
                for d, v, b in zip(datas, valids, self.blocks)
            ),
            mask,
        )

    def to_pylist(self, decode_strings: bool = True) -> List[tuple]:
        """Compact live rows to host python tuples (None for NULLs).
        Test/CLI/REST output path — not on the hot loop."""
        if isinstance(self.row_mask, jax.Array):
            return self.device_get().to_pylist(decode_strings)
        mask = np.asarray(self.row_mask)
        rows_idx = np.nonzero(mask)[0]
        cols = []
        for b in self.blocks:
            data = np.asarray(b.data)[rows_idx]
            valid = np.asarray(b.valid)[rows_idx]
            if b.type.is_string and b.dictionary is not None and decode_strings:
                vals = b.dictionary.decode(data)
            elif b.type.is_raw_string and decode_strings:
                from presto_tpu.ops.rawstring import decode_strings as _dec

                vals = np.asarray(_dec(data), dtype=object)
            elif b.type.is_array:
                from presto_tpu.ops.container import decode_arrays

                vals = np.empty(len(data), dtype=object)
                vals[:] = decode_arrays(data, b.type, b.dictionary)
            elif b.type.is_map:
                from presto_tpu.ops.container import decode_maps

                vals = np.empty(len(data), dtype=object)
                vals[:] = decode_maps(data, b.type, b.dictionary)
            elif b.type.name == "row":
                from presto_tpu.ops.container import decode_rows

                vals = np.empty(len(data), dtype=object)
                vals[:] = decode_rows(data, b.type)
            elif b.type.is_long_decimal:
                import decimal

                from presto_tpu.ops.decimal128 import decode_py

                vals = np.empty(len(data), dtype=object)
                with decimal.localcontext() as ctx:
                    ctx.prec = 50  # scaleb must not round 38-digit values
                    vals[:] = [decimal.Decimal(v).scaleb(-(b.type.scale or 0))
                               for v in decode_py(data)]
            elif b.type.is_decimal:
                # exact scaled-int values surface as decimal.Decimal —
                # floats would silently round p>15 results (the
                # reference returns java BigDecimal)
                import decimal

                sc = b.type.scale or 0
                vals = np.empty(len(data), dtype=object)
                vals[:] = [decimal.Decimal(int(v)).scaleb(-sc) for v in data]
            else:
                vals = data
            col = [None if not v else _to_py(vals[i], b.type) for i, v in enumerate(valid)]
            cols.append(col)
        return [tuple(c[i] for c in cols) for i in range(len(rows_idx))]

    def compact_host(self) -> "Page":
        """Host-side compaction: gather live rows to a prefix.  Pulls
        the page in ONE batched transfer and stays numpy — consumers
        that need device arrays re-upload on first use."""
        p = self.device_get() if isinstance(self.row_mask, jax.Array) else self
        mask = np.asarray(p.row_mask)
        idx = np.nonzero(mask)[0]
        n = len(idx)
        blocks = []
        for b in p.blocks:
            data = np.asarray(b.data)[idx]
            valid = np.asarray(b.valid)[idx]
            if n == 0:
                data = np.zeros((1,) + data.shape[1:], dtype=data.dtype)
                valid = np.zeros(1, dtype=np.bool_)
            blocks.append(Block(data, valid, b.type, b.dictionary))
        mask_out = np.zeros(max(n, 1), dtype=np.bool_)
        mask_out[:n] = True
        return Page(tuple(blocks), mask_out)

    def __repr__(self) -> str:
        return f"Page({self.num_blocks} blocks, capacity={self.capacity})"


def _to_py(v, t: Type):
    if t.name == "decimal":
        return v  # already decimal.Decimal (exact)
    if t.name == "double":
        return float(v)
    if t.name == "boolean":
        return bool(v)
    if t.name == "timestamp":
        import datetime

        return datetime.datetime(1970, 1, 1) + datetime.timedelta(microseconds=int(v))
    if t.name == "interval day to second":
        import datetime

        return datetime.timedelta(microseconds=int(v))
    if t.name == "interval year to month":
        return int(v)  # months (the reference renders 'Y-M')
    if t.is_string:
        return v  # already decoded (str) or raw code
    if isinstance(v, (np.integer,)):
        return int(v)
    return v


def concat_pages_host(pages: Sequence[Page]) -> Page:
    """Host-side concatenation of compacted pages (result assembly)."""
    pages = [p.compact_host() for p in pages]
    pages = [p for p in pages if int(np.asarray(p.row_mask).sum()) > 0] or pages[:1]
    ntypes = pages[0].types
    cols, valids, dicts = [], [], []
    for i, t in enumerate(ntypes):
        datas, vs = [], []
        for p in pages:
            n = int(np.asarray(p.row_mask).sum())
            datas.append(np.asarray(p.blocks[i].data)[:n])
            vs.append(np.asarray(p.blocks[i].valid)[:n])
        cols.append(np.concatenate(datas) if datas else np.zeros(0, t.np_dtype))
        valids.append(np.concatenate(vs) if vs else np.zeros(0, np.bool_))
        dicts.append(pages[0].blocks[i].dictionary)
    return Page.from_arrays(cols, ntypes, valids=valids, dictionaries=dicts)
