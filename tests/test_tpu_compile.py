"""Compiles for a v5e that is described, not attached: what the TPU's
compiler does to the programs, counted without a chip.

The sort path stacks every lane of a page into one sort kernel
(``ops/aggregate._sorted_runs``), because the TPU's sort code grows
with each operand it carries.  Its batches (``_batches``) exist to bound
what one sort keeps live on a large page; the TPU compiler merges sorts
of equal keys into one when nothing orders them, lanes and all, so each
batch's key carries a zero computed from the batch before
(``_zero_after``).  Here the merge of four ``avg`` states keeps one sort,
or four where the stack holds two lanes.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from presto_tpu.exec.local import concat_pages_device
from presto_tpu.expr.ir import AggCall, ColumnRef
from presto_tpu.ops import aggregate as A
from presto_tpu.page import Block, Page
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR, DecimalType


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache and cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _merge_text(sharding, rows):
    """The TPU's compiled text of the merge of two pages of four ``avg``
    states (``tpcds_sf10``'s ds_q07) of ``rows`` slots each."""
    dec = DecimalType(12, 2)
    aggs = [AggCall("avg", ColumnRef(BIGINT, 1), DOUBLE)] + [
        AggCall("avg", ColumnRef(dec, i), dec) for i in (2, 3, 4)]
    types = [VARCHAR] + [t for a in aggs for t in A.state_types(a)]

    def spec(dtype, shape=(rows,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    page = Page(tuple(Block(spec(t.np_dtype), spec(jnp.bool_), t)
                      for t in types), spec(jnp.bool_))

    def merge(a, b):
        return A.merge_aggregate(concat_pages_device([a, b]), 1, aggs,
                                 2 * rows, key_domains=[(0, 101999)],
                                 mode="partial")

    return jax.jit(merge).lower(page, page).compile().as_text()


def test_the_lanes_of_a_merge_ride_one_sort(one_chip, no_compile_cache):
    """Eight state lanes, one sort kernel: the stack's rows."""
    assert len(re.findall(r" sort\(", _merge_text(one_chip, 4096))) == 1


def test_the_batches_of_a_merge_stay_apart(one_chip, no_compile_cache,
                                           monkeypatch):
    """With two lanes a sort the four ``avg``s take four sorts, and the
    compiler keeps them four."""
    monkeypatch.setattr(A, "_SORT_STACK_BYTES", 2 * 32 * 2 * 4096)
    assert A._lanes_per_sort(2 * 4096) == 2
    assert len(A._batches([(2, 0, False)] * 4, 2)) == 4
    assert len(re.findall(r" sort\(", _merge_text(one_chip, 4096))) == 4
