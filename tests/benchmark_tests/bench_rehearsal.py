"""Shared by the benchmark's tier-1 tests: a temporary copy of
``benchmark/`` cut to SF0.01, and the command run from it on XLA:CPU
with ``--cpu-rehearsal``.

The copy changes two numbers in each configuration file (scale and
split size) and regenerates ``expected/`` with the copy's own
``reference/make_expected.py``; everything else, ``run.py`` included,
is the committed code.  ``presto_tpu`` comes from the repo through
``PYTHONPATH``.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCHMARK = os.path.join(REPO, "benchmark")
TINY_SF = 0.01
TINY_SPLIT_ROWS = 8192
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def make_copy(tmp: str) -> str:
    """``<tmp>/benchmark`` at SF0.01 with its expected answers; returns
    the checkout-like directory ``tmp``."""
    root = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCHMARK, root, ignore=shutil.ignore_patterns(
        ".cache", "trace_out", "__pycache__", "testdata"))
    for f in os.listdir(os.path.join(root, "configs")):
        path = os.path.join(root, "configs", f)
        with open(path) as fh:
            config = json.load(fh)
        config["scale_factor"] = TINY_SF
        config["split_rows"] = TINY_SPLIT_ROWS
        with open(path, "w") as fh:
            json.dump(config, fh)
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "reference", "make_expected.py"),
             "--config", config["name"]],
            env=env(), capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
    return tmp


def env(devices=None, pythonpath=REPO) -> dict:
    out = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if pythonpath is None:
        out.pop("PYTHONPATH", None)
    else:
        out["PYTHONPATH"] = pythonpath
    if devices is not None:
        out["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return out


def run_cell(checkout: str, cell: str, *, trace: int = 0, seed: int = 1,
             seconds: float = 2.0, rehearsal: bool = True, devices=None,
             pythonpath=REPO):
    """The command as the driver gives it, from ``checkout``; returns
    the finished process."""
    cmd = [sys.executable, os.path.join("benchmark", "run.py"),
           "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if rehearsal:
        cmd.append("--cpu-rehearsal")
    return subprocess.run(cmd, cwd=checkout, env=env(devices, pythonpath),
                          capture_output=True, text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
