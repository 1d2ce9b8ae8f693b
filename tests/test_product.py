"""Product tests — the presto-product-tests slot: BLACK-BOX suites
against a real multi-process cluster (separate coordinator + worker
OS processes launched from etc/ directories, like the reference's
Tempto suites against docker-compose clusters;
``presto-product-tests/bin/run_on_docker.sh``).  Everything goes
through public surfaces only: the launcher CLI, the REST protocol and
the packaged tarball — no in-process objects."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_etc(root, role: str, port: int, discovery: str = ""):
    etc = os.path.join(root, role)
    os.makedirs(os.path.join(etc, "catalog"), exist_ok=True)
    lines = [f"coordinator={'true' if role == 'coordinator' else 'false'}",
             f"http-server.http.port={port}"]
    if discovery:
        lines.append(f"discovery.uri={discovery}")
    with open(os.path.join(etc, "config.properties"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(etc, "catalog", "tpch.properties"), "w") as f:
        f.write("connector.name=tpch\ntpch.scale-factor=0.002\n"
                "tpch.split-rows=1024\n")
    return etc


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # product tests run on XLA:CPU
    return env


def _launcher(*args):
    return subprocess.run(
        [sys.executable, "-m", "presto_tpu.launcher", *args],
        capture_output=True, text=True, cwd=REPO, timeout=120, env=_env())


def _wait_http(uri: str, timeout: float = 60.0) -> None:
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(uri + "/v1/info", timeout=2) as r:
                r.read()
            return
        except Exception as e:
            last = e
            time.sleep(0.4)
    raise TimeoutError(f"{uri} never came up: {last}")


def _post_query(uri: str, sql: str):
    req = urllib.request.Request(
        uri + "/v1/statement", data=sql.encode(),
        headers={"X-Presto-User": "product-test"})
    rows, cols = [], None
    with urllib.request.urlopen(req, timeout=60) as r:
        payload = json.load(r)
    while True:
        if payload.get("columns") and cols is None:
            cols = [c["name"] for c in payload["columns"]]
        rows.extend(tuple(r) for r in payload.get("data") or [])
        nxt = payload.get("nextUri")
        if not nxt:
            break
        with urllib.request.urlopen(nxt, timeout=60) as r:
            payload = json.load(r)
    state = payload.get("stats", {}).get("state")
    if payload.get("error"):
        raise RuntimeError(payload["error"])
    return rows, cols, state


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """coordinator + worker as separate OS processes via the launcher
    daemon commands (pidfiles under etc/var)."""
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    root = str(tmp_path_factory.mktemp("product"))
    cport, wport = free_port(), free_port()
    cetc = _write_etc(root, "coordinator", cport)
    wetc = _write_etc(root, "worker", wport,
                      discovery=f"http://127.0.0.1:{cport}")
    assert _launcher("start", "--etc", cetc).returncode == 0
    assert _launcher("start", "--etc", wetc).returncode == 0
    curi = f"http://127.0.0.1:{cport}"
    wuri = f"http://127.0.0.1:{wport}"
    try:
        _wait_http(curi)
        _wait_http(wuri)
        yield {"root": root, "cetc": cetc, "wetc": wetc,
               "curi": curi, "wuri": wuri}
    finally:
        _launcher("stop", "--etc", wetc)
        _launcher("stop", "--etc", cetc)


def test_query_through_rest_protocol(cluster):
    rows, cols, state = _post_query(
        cluster["curi"],
        "SELECT o_orderpriority, count(*) AS c FROM orders "
        "GROUP BY o_orderpriority ORDER BY o_orderpriority")
    assert state == "FINISHED"
    assert cols == ["o_orderpriority", "c"]
    assert len(rows) == 5
    assert sum(c for _, c in rows) > 0


def test_launcher_status_and_pidfile(cluster):
    out = _launcher("status", "--etc", cluster["cetc"]).stdout
    assert out.startswith("running as ")
    pid = int(out.split()[-1])
    os.kill(pid, 0)  # alive
    assert os.path.exists(
        os.path.join(cluster["cetc"], "var", "launcher.pid"))
    # server log captured under var/log
    log = os.path.join(cluster["cetc"], "var", "log", "server.log")
    assert os.path.exists(log) and "listening" in open(log).read()


def test_worker_info_and_graceful_shutdown(cluster):
    # worker serves the info endpoint
    with urllib.request.urlopen(cluster["wuri"] + "/v1/info",
                                timeout=5) as r:
        info = json.load(r)
    assert "uptime" in json.dumps(info).lower() or info
    # graceful shutdown: PUT state SHUTTING_DOWN drains and exits
    req = urllib.request.Request(
        cluster["wuri"] + "/v1/info/state",
        data=json.dumps("SHUTTING_DOWN").encode(),
        headers={"Content-Type": "application/json"}, method="PUT")
    with urllib.request.urlopen(req, timeout=10) as r:
        r.read()
    deadline = time.time() + 30
    down = False
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(cluster["wuri"] + "/v1/info",
                                        timeout=2) as r:
                r.read()
            time.sleep(0.5)
        except Exception:
            down = True
            break
    assert down, "worker did not exit after graceful shutdown"
    # coordinator stays healthy for queries
    rows, _, state = _post_query(cluster["curi"],
                                 "SELECT count(*) FROM nation")
    assert state == "FINISHED" and rows[0][0] == 25


def test_package_tarball_launches(tmp_path):
    """presto-server tarball slot: assemble the package, unpack it
    elsewhere, launch from the packaged bin/launcher, query it."""
    out = subprocess.run(["bash", "tools/package.sh"], cwd=REPO,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    tarball = os.path.join(REPO, out.stdout.strip().splitlines()[-1])
    assert os.path.exists(tarball)
    subprocess.run(["tar", "xzf", tarball, "-C", str(tmp_path)], check=True)
    (pkg,) = [d for d in os.listdir(tmp_path)
              if d.startswith("presto-tpu-")]
    pkgdir = os.path.join(str(tmp_path), pkg)
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    run = subprocess.run(
        [os.path.join(pkgdir, "bin", "launcher"), "start",
         "--port", str(port)],
        capture_output=True, text=True, timeout=120, env=_env(), cwd=pkgdir)
    assert run.returncode == 0, run.stderr
    try:
        uri = f"http://127.0.0.1:{port}"
        _wait_http(uri)
        rows, _, state = _post_query(uri, "SELECT count(*) FROM region")
        assert state == "FINISHED" and rows[0][0] == 5
    finally:
        subprocess.run([os.path.join(pkgdir, "bin", "launcher"), "stop"],
                       capture_output=True, text=True, timeout=60,
                       cwd=pkgdir)
