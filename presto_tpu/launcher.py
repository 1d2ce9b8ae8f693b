"""Server launcher: start a coordinator or worker from an etc/ directory.

Reference analog: ``presto-server``'s bin/launcher + PrestoServer.java
bootstrap (role selection via config.properties ``coordinator=true``,
catalogs from etc/catalog/*.properties).  Usage:

  python -m presto_tpu.launcher run --etc etc/            # foreground
  python -m presto_tpu.launcher run --etc etc/ --port 8080

A coordinator serves the V1 statement protocol (server/coordinator.py);
a worker serves the task protocol (server/worker.py).  Workers register
with the coordinator via ``discovery.uri`` the way reference workers
announce to airlift discovery.
"""

from __future__ import annotations

import argparse
import signal
import sys


def build_from_etc(etc_dir: str, port: int = 0):
    from presto_tpu.config import EngineConfig
    from presto_tpu.runner import QueryRunner

    cfg = EngineConfig.from_etc(etc_dir)
    catalog = cfg.build_catalog()
    # persistent XLA program cache: a restarted coordinator/worker
    # rehydrates compiled query programs from disk instead of paying
    # the cold-start compile tax again (exec/programs.py)
    from presto_tpu.exec.programs import enable_persistent_cache

    enable_persistent_cache()
    # observability wiring: query.trace-dir turns tracing on and drops
    # one Chrome-trace JSON per query; query.log-path attaches the
    # JSONL query-log EventListener (docs/observability.md)
    from presto_tpu import obs

    obs.maybe_enable_trace_dir(cfg)
    # deterministic fault injection (testing_faults.py): inert unless
    # the PRESTO_TPU_FAULTS/_FAULT_SEED env pair arms it — the chaos
    # legs' entry point, a no-op in production
    from presto_tpu.testing_faults import arm_from_env

    arm_from_env()
    port = port or cfg.int("http-server.http.port", 0)
    # serving-tier cache budget (query.result-cache-bytes overrides
    # the PRESTO_TPU_RESULT_CACHE_BYTES / 64 MiB process default)
    from presto_tpu.serving.cache import set_result_cache_bytes

    set_result_cache_bytes(cfg.result_cache_bytes(0))
    if cfg.bool("coordinator", True):
        from presto_tpu.server.coordinator import CoordinatorServer

        runner = QueryRunner(catalog, session=cfg.build_session())
        log_path = cfg.query_log_path()
        if log_path:
            runner.events.add(obs.QueryLogListener(log_path))
        # coordinator.worker-uris (comma-separated) feeds the worker
        # plane: the failure detector's heartbeats, /v1/worker +
        # system_runtime_workers + the web-UI worker list, the memory
        # manager's remote polls and system_metrics' per-node rows —
        # without it a launcher-built coordinator has no fleet to watch
        worker_uris = [u.strip()
                       for u in cfg.str("coordinator.worker-uris",
                                        "").split(",") if u.strip()]
        server = CoordinatorServer(
            runner, port=port, worker_uris=worker_uris,
            # query.max-execution-time / query.max-queued-time: the
            # deadline plane (docs/fault-tolerance.md; the deadline is
            # opt-in, the queue bound replaces the hard-coded 600s)
            max_execution_time=cfg.max_execution_time(),
            max_queued_time=cfg.max_queued_time(),
            # serving-tier admission knobs (docs/serving.md): memory
            # gate fraction + default projection for unseen statements
            admission_memory_fraction=cfg.admission_memory_fraction(),
            admission_reserve_bytes=cfg.admission_reserve_bytes())
        role = "coordinator"
    else:
        from presto_tpu.memory import default_memory_pool
        from presto_tpu.server.worker import WorkerServer

        # the process HBM pool: gives a deployed worker the memory
        # accounting surfaces (/v1/info breakdown, memory.pool_* gauges
        # on /v1/metrics) the coordinator's killer and the metrics
        # plane read
        server = WorkerServer(
            catalog,
            port=port,
            buffer_bytes=cfg.int("task.buffer-bytes", 64 << 20),
            memory_pool=default_memory_pool(),
            # morsel split scheduler width for fragment scans (0 =
            # process default from PRESTO_TPU_TASK_CONCURRENCY)
            task_concurrency=cfg.int("query.task-concurrency", 0) or None,
        )
        role = "worker"
    return server, role, cfg


def _var_paths(etc_dir: str):
    import os

    var = os.path.join(etc_dir, "var")
    os.makedirs(os.path.join(var, "log"), exist_ok=True)
    return (os.path.join(var, "launcher.pid"),
            os.path.join(var, "log", "server.log"))


def _read_pid(pidfile: str):
    import os

    try:
        with open(pidfile) as f:
            pid = int(f.read().strip())
    except (FileNotFoundError, ValueError):
        return None
    try:
        os.kill(pid, 0)  # alive?
    except ProcessLookupError:
        return None
    except PermissionError:
        pass  # EPERM: alive, owned by another user
    return pid


def daemon_start(etc_dir: str, port: int = 0) -> int:
    """bin/launcher ``start``: detach a ``run`` child, record its pid
    (the reference launcher's pidfile + var/log/server.log contract)."""
    import os
    import subprocess

    pidfile, logfile = _var_paths(etc_dir)
    pid = _read_pid(pidfile)
    if pid is not None:
        print(f"already running as {pid}")
        return pid
    cmd = [sys.executable, "-m", "presto_tpu.launcher", "run",
           "--etc", etc_dir]
    if port:
        cmd += ["--port", str(port)]
    with open(logfile, "ab") as log:
        child = subprocess.Popen(cmd, stdout=log, stderr=log,
                                 start_new_session=True,
                                 cwd=os.getcwd())
    with open(pidfile, "w") as f:
        f.write(str(child.pid))
    print(f"started as {child.pid}")
    return child.pid


def daemon_stop(etc_dir: str, timeout: float = 30.0) -> bool:
    """bin/launcher ``stop``: SIGTERM then wait (the server drains)."""
    import os
    import time

    pidfile, _ = _var_paths(etc_dir)
    pid = _read_pid(pidfile)
    if pid is None:
        print("not running")
        return True
    try:
        os.kill(pid, signal.SIGTERM)
    except ProcessLookupError:  # exited between check and signal
        os.unlink(pidfile)
        print("stopped")
        return True
    except PermissionError:
        # recycled pid now owned by another user: never signal it
        print(f"pid {pid} is not ours (stale pidfile?); not signalling")
        return False
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            os.unlink(pidfile)
            print("stopped")
            return True
        time.sleep(0.1)
    try:
        os.kill(pid, signal.SIGKILL)
    except ProcessLookupError:
        pass  # exited in the last poll window
    os.unlink(pidfile)
    print("killed")
    return False


def daemon_status(etc_dir: str):
    pidfile, _ = _var_paths(etc_dir)
    pid = _read_pid(pidfile)
    print(f"running as {pid}" if pid else "not running")
    return pid


def main(argv=None):
    ap = argparse.ArgumentParser(prog="presto_tpu.launcher", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run", help="run the server in the foreground")
    run.add_argument("--etc", required=True, help="etc/ config directory")
    run.add_argument("--port", type=int, default=0)
    for name in ("start", "stop", "restart", "status"):
        p = sub.add_parser(name, help=f"daemon {name} (pidfile under etc/var)")
        p.add_argument("--etc", required=True)
        if name in ("start", "restart"):
            p.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)

    if args.cmd == "start":
        daemon_start(args.etc, args.port)
        return
    if args.cmd == "stop":
        daemon_stop(args.etc)
        return
    if args.cmd == "restart":
        daemon_stop(args.etc)
        daemon_start(args.etc, args.port)
        return
    if args.cmd == "status":
        daemon_status(args.etc)
        return

    server, role, cfg = build_from_etc(args.etc, args.port)
    server.start()
    uri = server.uri
    print(f"{role} listening at {uri}", flush=True)

    stop = {"flag": False}

    def on_term(sig, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, on_term)
    signal.signal(signal.SIGTERM, on_term)
    import time

    while not stop["flag"]:
        time.sleep(0.2)
    # workers drain (finish running tasks) before exiting
    if hasattr(server, "drain"):
        server.drain(timeout=cfg.int("shutdown.grace-seconds", 30))
    else:
        server.stop()
    print(f"{role} stopped", flush=True)


if __name__ == "__main__":
    main()
