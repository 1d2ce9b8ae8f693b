"""Tier-1 runs what the chip selects.

Two kernel choices are keyed to the backend's name: the CSR
direct-address join table (ops/join.py ``resolve_direct_join``) and the
masked segment reduction (ops/aggregate.py
``_masked_segments_profitable``).  Tier-1 runs on XLA:CPU, which picks
the other leg of each, so without this file the legs a TPU takes are
run by no test.  Here the existing hooks force them and the answers are
checked against the sqlite oracle.

Q1, Q3 and Q14 are the chip smoke's queries: Q1 and Q14 take the masked
reduction.  Their joins have primary-key builds and take the sort-free
unique path on every backend, so Q4 and Q13 (non-unique builds over a
dense key domain) are here for the CSR table."""

import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec.programs import ProgramRegistry
from presto_tpu.ops import aggregate, join
from presto_tpu.runner import QueryRunner

from tests.oracle import assert_rows_match, load_oracle, run_oracle
from tests.tpch_queries import QUERIES


@pytest.fixture(scope="module")
def env():
    tpch = Tpch(sf=0.01, split_rows=16384)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    # a registry of its own: programs traced under the forced legs must
    # neither reuse nor leak into the suite's shared program space
    runner = QueryRunner(catalog, programs=ProgramRegistry())
    return runner, load_oracle(tpch)


@pytest.fixture
def chip_legs(monkeypatch):
    taken = {"csr": 0, "masked": 0}

    def csr():
        taken["csr"] += 1
        return join.resolve_direct_join()

    def masked():
        taken["masked"] += 1
        return True

    join.set_direct_join_override(True)
    monkeypatch.setattr(join, "_direct_table_profitable", csr)
    monkeypatch.setattr(aggregate, "_masked_segments_profitable", masked)
    yield taken
    join.set_direct_join_override(None)


@pytest.mark.parametrize("qid,leg", [
    (1, "masked"), (3, None), (14, "masked"), (4, "csr"), (13, "csr")])
def test_chip_legs_match_oracle(env, chip_legs, qid, leg):
    runner, oracle = env
    actual = runner.execute(QUERIES[qid]).rows
    assert_rows_match(actual, run_oracle(oracle, QUERIES[qid]),
                      ordered=False)
    if leg is not None:
        assert chip_legs[leg] > 0, f"Q{qid} never reached the {leg} leg"
