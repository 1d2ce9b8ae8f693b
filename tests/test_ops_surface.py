"""Transactions, trace tokens, config files, drain, benchmark driver.

Reference analogs: transaction/TransactionManager.java,
server/GenerateTraceTokenRequestFilter.java, airlift @Config etc/
bootstrap, server/GracefulShutdownHandler.java, presto-benchmark-driver.
"""

import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.page import Page
from presto_tpu.runner import QueryRunner
from presto_tpu.transaction import TransactionError
from presto_tpu.types import BIGINT


def make_runner():
    mem = MemoryConnector()
    mem.create_table(
        "t", [("x", BIGINT)],
        [Page.from_arrays([np.arange(5, dtype=np.int64)], [BIGINT])],
    )
    cat = Catalog()
    cat.register("mem", mem)
    return QueryRunner(cat), mem


# ---------------------------------------------------------------------------
# transactions
# ---------------------------------------------------------------------------

def test_commit_publishes_staged_writes():
    r, mem = make_runner()
    r.execute("START TRANSACTION")
    r.execute("INSERT INTO t SELECT x + 10 FROM t")
    # read-committed: staged write invisible before commit
    assert r.execute("SELECT count(*) FROM t").rows == [(5,)]
    r.execute("COMMIT")
    assert r.execute("SELECT count(*) FROM t").rows == [(10,)]


def test_rollback_discards_staged_writes():
    r, mem = make_runner()
    r.execute("START TRANSACTION")
    r.execute("INSERT INTO t SELECT x FROM t")
    r.execute("CREATE TABLE t2 AS SELECT x FROM t")
    r.execute("ROLLBACK")
    assert r.execute("SELECT count(*) FROM t").rows == [(5,)]
    assert "t2" not in mem.table_names()


def test_read_only_transaction_rejects_writes():
    r, _ = make_runner()
    r.execute("START TRANSACTION READ ONLY")
    with pytest.raises(TransactionError):
        r.execute("INSERT INTO t SELECT x FROM t")
    r.execute("ROLLBACK")


def test_transaction_state_errors():
    r, _ = make_runner()
    with pytest.raises(TransactionError):
        r.execute("COMMIT")
    r.execute("START TRANSACTION")
    with pytest.raises(TransactionError):
        r.execute("START TRANSACTION")
    r.execute("COMMIT")
    assert r.transactions.open_count() == 0


def test_staged_drop_applies_at_commit():
    r, mem = make_runner()
    r.execute("START TRANSACTION")
    r.execute("DROP TABLE t")
    assert "t" in mem.table_names()
    r.execute("COMMIT")
    assert "t" not in mem.table_names()


# ---------------------------------------------------------------------------
# trace tokens
# ---------------------------------------------------------------------------

def test_trace_token_propagates_to_events():
    from presto_tpu.events import EventListener

    r, _ = make_runner()
    seen = {}

    class L(EventListener):
        def query_created(self, e):
            seen["created"] = e.trace_token

        def query_completed(self, e):
            seen["completed"] = e.trace_token

    r.events.add(L())
    r.session.trace_token = "trace_test123"
    r.execute("SELECT count(*) FROM t")
    assert seen == {"created": "trace_test123", "completed": "trace_test123"}


def test_trace_token_generated_when_absent():
    from presto_tpu.events import EventListener

    r, _ = make_runner()
    seen = {}

    class L(EventListener):
        def query_created(self, e):
            seen["tok"] = e.trace_token

    r.events.add(L())
    r.execute("SELECT count(*) FROM t")
    assert seen["tok"] and seen["tok"].startswith("trace_")


# ---------------------------------------------------------------------------
# config files
# ---------------------------------------------------------------------------

def test_config_properties_parsing(tmp_path):
    from presto_tpu.config import EngineConfig

    etc = tmp_path / "etc"
    (etc / "catalog").mkdir(parents=True)
    (etc / "config.properties").write_text(
        "# role\ncoordinator=true\nhttp-server.http.port=8080\n"
        "session.max_groups=4096\n"
    )
    (etc / "catalog" / "tiny.properties").write_text(
        "connector.name=tpch\ntpch.scale-factor=0.001\n"
    )
    cfg = EngineConfig.from_etc(str(etc))
    assert cfg.bool("coordinator") is True
    assert cfg.int("http-server.http.port") == 8080
    assert cfg.session_defaults() == {"max_groups": "4096"}

    catalog = cfg.build_catalog()
    session = cfg.build_session()
    assert session.get("max_groups") == 4096
    r = QueryRunner(catalog, session=session)
    assert r.execute("SELECT count(*) FROM tiny.region").rows == [(5,)]
    assert r.execute("SELECT count(*) FROM region").rows == [(5,)]


def test_malformed_property_line_raises():
    from presto_tpu.config import parse_properties

    with pytest.raises(ValueError):
        parse_properties("not a property")


# ---------------------------------------------------------------------------
# graceful shutdown drain
# ---------------------------------------------------------------------------

def test_worker_drain_rejects_new_tasks():
    import json as _json
    import urllib.request

    from presto_tpu.server.worker import WorkerServer

    mem = MemoryConnector()
    mem.create_table(
        "t", [("x", BIGINT)],
        [Page.from_arrays([np.arange(3, dtype=np.int64)], [BIGINT])],
    )
    cat = Catalog()
    cat.register("mem", mem)
    w = WorkerServer(cat)
    w.start()
    try:
        req = urllib.request.Request(
            w.uri + "/v1/info/state", data=b'"SHUTTING_DOWN"', method="PUT")
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert resp.status == 200
        # state reflects the drain
        import time

        deadline = time.time() + 5
        state = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(w.uri + "/v1/info", timeout=5) as resp:
                    state = _json.loads(resp.read())["state"]
                if state == "SHUTTING_DOWN":
                    break
            except Exception:
                break  # server already stopped post-drain — acceptable
            time.sleep(0.05)
    finally:
        try:
            w.stop()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# scaled writers
# ---------------------------------------------------------------------------

def test_scaled_writer_scales_and_orders():
    import time

    from presto_tpu.writer import ScaledWriter

    w = ScaledWriter(lambda x: (time.sleep(0.02), x * 10)[1],
                     max_writers=4, scale_depth=1)
    for i in range(20):
        w.submit(i)
    out = w.finish()
    assert out == [i * 10 for i in range(20)]
    assert w.writer_count > 1  # queue depth triggered extra writers


def test_scaled_writer_error_propagates():
    from presto_tpu.writer import ScaledWriter

    w = ScaledWriter(lambda x: 1 / 0)
    w.submit(1)
    with pytest.raises(ZeroDivisionError):
        w.finish()


def test_ctas_multisplit_preserves_splits():
    """A multi-split source CTAS lands as a multi-split table (parallel
    writers, one split per produced page)."""
    from presto_tpu.connectors.tpch import Tpch

    cat = Catalog()
    cat.register("tpch", Tpch(sf=0.01, split_rows=1 << 12))
    mem = MemoryConnector()
    cat.register("mem", mem, writable=True)
    r = QueryRunner(cat)
    r.execute("CREATE TABLE li2 AS SELECT l_orderkey, l_quantity FROM lineitem")
    assert mem.num_splits("li2") > 1
    got = r.execute("SELECT count(*), sum(l_quantity) FROM li2").rows
    want = r.execute("SELECT count(*), sum(l_quantity) FROM lineitem").rows
    assert got == want


# ---------------------------------------------------------------------------
# launcher / packaging
# ---------------------------------------------------------------------------

def test_launcher_coordinator_from_etc(tmp_path):
    from presto_tpu.client import StatementClient
    from presto_tpu.launcher import build_from_etc

    etc = tmp_path / "etc"
    (etc / "catalog").mkdir(parents=True)
    (etc / "config.properties").write_text("coordinator=true\n")
    (etc / "catalog" / "tiny.properties").write_text(
        "connector.name=tpch\ntpch.scale-factor=0.001\n")
    server, role, _ = build_from_etc(str(etc))
    assert role == "coordinator"
    server.start()
    try:
        _, rows = StatementClient(server.uri).execute("SELECT count(*) FROM region")
        assert rows == [(5,)]
    finally:
        server.stop()


def test_launcher_worker_role(tmp_path):
    from presto_tpu.launcher import build_from_etc

    etc = tmp_path / "etc"
    (etc / "catalog").mkdir(parents=True)
    (etc / "config.properties").write_text("coordinator=false\n")
    server, role, _ = build_from_etc(str(etc))
    assert role == "worker"
    server.start()
    try:
        import json as _json
        import urllib.request

        with urllib.request.urlopen(server.uri + "/v1/info", timeout=5) as resp:
            assert _json.loads(resp.read())["state"] == "ACTIVE"
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# benchmark driver
# ---------------------------------------------------------------------------

def test_benchmark_driver_runs_suite():
    import subprocess
    import sys
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "benchmark_driver.py"),
         "--suite", "tpch", "--queries", "q1,q6", "--sf", "0.001",
         "--runs", "1", "--json"],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    import json as _json

    rows = [_json.loads(l) for l in proc.stdout.decode().splitlines()]
    assert {r["query"] for r in rows} == {"q1", "q6"}
    assert all("median_s" in r for r in rows)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_chip_scripts_refuse_a_cpu(script):
    """Neither script measures or passes on a device that is not a
    TPU: non-zero exit, the device found named, no result line."""
    import subprocess
    import sys
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, script)], cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'platform': 'cpu'" in proc.stderr, proc.stderr[-500:]
    assert proc.stdout.strip() == ""


def test_chip_smoke_last_line_is_the_verdict():
    """The last line of a passing smoke's standard output is exactly
    ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the
    observations are the line before it.  Run as the explicit CPU
    rehearsal, which says so in ``platform``."""
    import json as _json
    import subprocess
    import sys
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"),
         "--cpu-rehearsal", "--sf", "0.01"], cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    observations, verdict = map(_json.loads, proc.stdout.splitlines())
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    device = verdict["device"]
    assert set(device) == {"platform", "kind", "count"}
    assert device["platform"] == "cpu" and isinstance(device["kind"], str)
    assert type(device["count"]) is int and device["count"] >= 1
    assert observations["device"] == device
    assert observations["reduced"] and set(observations["one_chip"]["queries"]) \
        == {"q6", "q14", "q1", "q3"}
