"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's DistributedQueryRunner approach (SURVEY.md §4.5:
one JVM hosting coordinator + N workers) — here one process hosting an
8-device virtual TPU topology via XLA's host-platform device count, so
multi-chip sharding is exercised without hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Static plan/IR validation always-on for the whole suite: every query
# any test plans through a QueryRunner also runs the analysis tier
# (presto_tpu/analysis/), so a type/null-mask/ladder invariant break
# fails the suite with a node-specific diagnostic instead of a kernel
# crash.  setdefault: an explicit =0 in the environment still wins.
os.environ.setdefault("PRESTO_TPU_VALIDATE_PLANS", "1")
# ... and every optimizer rule application runs the rewrite-soundness
# gate (presto_tpu/analysis/soundness.py): an unsound rewrite fails
# the suite naming the rule, not as a wrong answer downstream
os.environ.setdefault("PRESTO_TPU_VALIDATE_REWRITES", "1")
# ... and every bound plan runs the expression-tier abstract
# interpreter (presto_tpu/analysis/kernel_soundness.py): a provable
# overflow, lossy cast, literal zero divisor, wrapping accumulator, or
# null-policy mismatch fails the suite with node-level attribution
os.environ.setdefault("PRESTO_TPU_VALIDATE_KERNELS", "1")


def host_cache_dir(root: str) -> str:
    import hashlib
    import platform

    tag = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    tag = hashlib.sha256(line.encode()).hexdigest()[:12]
                    break
    except OSError:
        pass
    return os.path.join(root, tag)


# Persistent compilation cache: the suite's wall-clock is dominated by
# XLA recompilation (every query/capacity pair is a fresh program), so
# compiled executables are cached on disk across runs and processes.
# The engine's rule (exec/programs.enable_persistent_cache) is: where
# JAX_COMPILATION_CACHE_DIR points, else <checkout>/.jax_cache.  The
# suite places it from outside like any deployment would, exported
# before jax is imported so subprocess tests inherit it: a
# sub-directory keyed by a CPU-feature fingerprint, because rounds run
# on heterogeneous driver hosts and replaying XLA:CPU executables
# AOT-compiled for another host's avx512/amx feature set
# SIGILLs/segfaults (observed r5: a 21k-entry cache from a prior host
# crashed the suite mid-write).  A value already in the environment
# wins.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    host_cache_dir(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")))

import jax

# tier-1 always runs on XLA:CPU, whatever the machine holds
jax.config.update("jax_platforms", "cpu")
# float64/int64 for DOUBLE/BIGINT columns on the CPU test backend.
jax.config.update("jax_enable_x64", True)
# NOTE: deliberately NOT enabling jax_persistent_cache_enable_xla_caches:
# XLA:CPU kernel caches are AOT-compiled for this host's CPU features and
# replaying them on a different machine can SIGILL; the jit cache alone
# is portable (it keys on the platform) and captures most of the win.


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-worker/chaos tests excluded from the tier-1 "
        "sweep (-m 'not slow'); tools/ci.sh runs them in dedicated legs",
    )
