"""Shape-canonicalizing program registry + persistent XLA cache.

Reference analog: ``sql/gen/ExpressionCompiler.java:53`` — the
reference keys generated operator bytecode by a *structural* cache key
(RowExpression + compiler flags), so two queries whose filters compile
to the same bytecode share one class.  This repo's executor instead
cached one jitted callable per ``PlanNode`` *object*
(``exec/local.py``), so two structurally identical aggregations in
different queries — or the same query re-planned after a write —
compiled twice, and every process started from zero.  Cold-start
compiles are the dominant latency tax of the XLA execution tier
(VERDICT checklist #1: q3 spent 30s of warmup in compiles at r5).

Two layers collapse that cost:

- :class:`ProgramRegistry` keys compiled executables by a structural
  signature — kernel family + the canonicalized expression IR + every
  parameter the closure bakes in (capacities, key domains, join kind,
  dictionaries) — so identical operator shapes share one traced
  callable across queries, plans, and runner rebuilds.  XLA program
  identity *within* a callable is then jit's own cache: input pytree
  statics (types, dictionaries) + shapes, which the pow2/64K shape
  ladder (``exec/local.py bucket_capacity``) keeps small.

- The JAX persistent compilation cache serializes compiled XLA
  binaries to disk so a *fresh process* — a restarted worker, the next
  benchmark or smoke run, a test run — rehydrates executables instead
  of recompiling.  One placement rule (:func:`enable_persistent_cache`):
  where ``JAX_COMPILATION_CACHE_DIR`` points when it is set, else
  ``<checkout>/.jax_cache``.

Both layers export counters (distinct programs, registry hits/misses,
cumulative compile seconds, persistent hits) surfaced by ``EXPLAIN
ANALYZE VERBOSE`` and dumped by ``tools/benchmark_driver.py
--cold-compile-report``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from presto_tpu.sync import named_lock

# ---------------------------------------------------------------------------
# structural signatures
# ---------------------------------------------------------------------------

# Dictionary objects are identity-hashed (page.py).  Signatures need a
# token that is stable for the object's lifetime AND never aliases a
# dead dictionary's id — so the token table holds a strong reference.
# Table-metadata dictionaries are few, but derived ones (per-literal
# string arrays) scale with query diversity, so the table is a bounded
# LRU with MONOTONIC token numbers: evicting an entry only means a
# re-appearing dictionary gets a FRESH token (a recompile, never a
# collision — the id-vs-object check below catches reused ids too).
# (identity-keyed fallback signatures share this table: an evicted or
# dead object's id re-emerging maps to a fresh monotonic token, so a
# stale registry entry goes unused instead of colliding)
_DICT_TOKENS_MAX = 4096
_DICT_TOKENS: "Dict[int, Tuple[object, int]]" = {}
_DICT_SEQ = [0]
_DICT_LOCK = named_lock("programs._DICT_LOCK")


def _dict_token(d) -> int:
    with _DICT_LOCK:
        ent = _DICT_TOKENS.get(id(d))
        if ent is None or ent[0] is not d:
            _DICT_SEQ[0] += 1
            ent = (d, _DICT_SEQ[0])
            _DICT_TOKENS[id(d)] = ent
            while len(_DICT_TOKENS) > _DICT_TOKENS_MAX:
                _DICT_TOKENS.pop(next(iter(_DICT_TOKENS)))
        return ent[1]


def type_signature(t) -> tuple:
    """Full structural identity of a Type.  ``Type.__repr__`` is lossy
    (it hides the dictionary flag and raw-varchar width), and raw vs
    dictionary VARCHAR compile to different kernels — so signatures
    use every identity-bearing field."""
    if t is None:
        return ()
    return (
        t.name, str(t.np_dtype), t.dictionary, t.scale, t.precision,
        type_signature(t.element), type_signature(t.key_element),
        tuple(type_signature(f) for f in t.fields) if t.fields else None,
        t.field_names,
    )


def ir_signature(obj) -> Any:
    """Hashable structural signature of expression IR / plan parameters.

    Walks dataclasses field-by-field (Expr, AggCall, WindowFunc, ...),
    expands Types fully, tokens Dictionaries by identity, and converts
    sequences to tuples.  Anything unrecognized is keyed by object
    identity and pinned so the id can never alias — identity keys
    merely forgo sharing, they never produce a wrong hit."""
    from presto_tpu.page import Dictionary
    from presto_tpu.types import Type

    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, Type):
        return ("T",) + type_signature(obj)
    if isinstance(obj, Dictionary):
        return ("D", _dict_token(obj))
    if isinstance(obj, (list, tuple)):
        return tuple(ir_signature(x) for x in obj)
    if isinstance(obj, (set, frozenset)):
        return ("S",) + tuple(sorted(map(ir_signature, obj), key=repr))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            ir_signature(getattr(obj, f.name))
            for f in dataclasses.fields(obj))
    return ("I", type(obj).__name__, _dict_token(obj))


# Plan-node fields excluded from the CROSS-PROCESS structural signature:
# they vary between the coordinator's plan and the fragment a worker
# executes (the coordinator assigns `splits` per worker; Precomputed
# stage results carry a materialized `page`) without changing what the
# operator *is* — including them would make worker actuals unmergeable
# with coordinator estimates.
_VOLATILE_FIELDS = {
    "TableScanNode": {"splits"},
    "PrecomputedNode": {"page"},
}


def stable_signature(obj) -> Any:
    """``ir_signature`` minus every per-process identity source: a
    signature that is equal for structurally equal plans ACROSS
    processes, so a worker's per-node stats can be merged onto the
    coordinator's entries by key alone (estimate-vs-actual roll-up,
    plan-history store).

    Differences from :func:`ir_signature` (which must stay
    identity-precise for program-cache correctness): Dictionaries
    collapse to a bare marker instead of an identity token, unknown
    objects key by type name only, and per-dispatch volatile plan
    fields (``splits``, materialized stage pages) are skipped.  That
    trades some precision for portability — exactly right for stats
    keys, where structural twins merging is the point, and exactly
    wrong for compiled-program keys, where it would alias kernels."""
    from presto_tpu.page import Dictionary
    from presto_tpu.types import Type

    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, Type):
        return ("T",) + type_signature(obj)
    if isinstance(obj, Dictionary):
        return "D"
    if isinstance(obj, (list, tuple)):
        return tuple(stable_signature(x) for x in obj)
    if isinstance(obj, (set, frozenset)):
        return ("S",) + tuple(sorted(map(stable_signature, obj), key=repr))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        name = type(obj).__name__
        skip = _VOLATILE_FIELDS.get(name, ())
        return (name,) + tuple(
            stable_signature(getattr(obj, f.name))
            for f in dataclasses.fields(obj) if f.name not in skip)
    return ("I", type(obj).__name__)


def structural_digest(node) -> str:
    """16-hex-char digest of a plan node's stable structural signature
    — the JSON-safe half of the ``(signature, occurrence)`` stats key
    shared by the coordinator, every worker, and the persisted
    plan-history store.  sha1 over the signature's repr: ``hash()`` is
    salted per process and identity tokens are per-process counters,
    so neither survives serialization; this does."""
    import hashlib

    return hashlib.sha1(
        repr(stable_signature(node)).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR``
#: does not say.  A fixed path: a directory named after a tmpdir, a
#: pid or a data root is empty in every new process and never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_PERSISTENT = {"wired": False, "hits": 0, "misses": 0}
_PERSISTENT_LOCK = named_lock("programs._PERSISTENT_LOCK")


def _cache_event_listener(event: str, **kwargs) -> None:
    # jax records a miss when it WRITES the entry it just compiled;
    # with the thresholds below every miss is written
    if event == "/jax/compilation_cache/cache_hits":
        _PERSISTENT["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _PERSISTENT["misses"] += 1


def enable_persistent_cache() -> Optional[str]:
    """The one rule for where compiled XLA binaries persist, shared by
    every entry point (LocalRunner, the launcher, bench.py,
    chip_smoke.py, tools/benchmark_driver.py):

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it by itself and no
      directory is set in code, so a deployment (or the chip tool)
      places the cache from outside;
    - not set: :data:`CHECKOUT_CACHE_DIR`.

    Returns the directory in effect."""
    import jax

    if _PERSISTENT["wired"]:  # runner construction is hot
        return jax.config.jax_compilation_cache_dir
    with _PERSISTENT_LOCK:
        if not _PERSISTENT["wired"]:
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir",
                                  CHECKOUT_CACHE_DIR)
            # jax's default thresholds skip small and fast programs —
            # exactly the chain programs a SQL workload compiles by
            # the hundred; persist everything
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.monitoring.register_event_listener(_cache_event_listener)
            _PERSISTENT["wired"] = True
    return jax.config.jax_compilation_cache_dir


def persistent_cache_stats() -> Dict[str, Any]:
    import jax

    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "persistent_hits": _PERSISTENT["hits"],
        "persistent_misses": _PERSISTENT["misses"],
    }


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


class Program:
    """A registered callable + its compile accounting.

    Wraps the (usually jitted) function; every call samples the jit
    trace-cache size, so a growing cache marks a compile event and the
    call's wall time is attributed to ``compile_s`` (trace+compile
    dominate a cold first call; steady-state calls add two cheap
    counter reads)."""

    __slots__ = ("fn", "kind", "jitted", "calls", "compile_s", "_registry")

    def __init__(self, fn: Callable, kind: str, jitted: bool, registry):
        self.fn = fn
        self.kind = kind
        self.jitted = jitted
        self.calls = 0
        self.compile_s = 0.0
        self._registry = registry

    def _cache_size(self) -> int:
        if not self.jitted:
            return 1
        try:
            return self.fn._cache_size()
        except Exception:
            return 1

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if not self.jitted:
            return self.fn(*args, **kwargs)
        n0 = self._cache_size()
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        n1 = self._cache_size()
        if n1 > n0:
            dt = time.perf_counter() - t0
            self.compile_s += dt
            reg = self._registry
            if reg is not None:
                with reg._lock:
                    reg.compile_s += dt
                    reg.trace_events += 1
            # the compile becomes a span in the active query's trace
            # (retroactive: detected only after the call returned) and
            # feeds the process-wide XLA counters — "how much of this
            # query was XLA compile" is the headline TPU question
            from presto_tpu.obs import METRICS, current_tracer

            METRICS.counter("xla.programs_compiled").inc(n1 - n0)
            METRICS.counter("xla.compile_seconds_total").inc(dt)
            METRICS.histogram("xla.compile_ms").observe(dt * 1e3)
            tr = current_tracer()
            if tr is not None:
                tr.add_complete("xla_compile", "compile", t0, dt,
                                kind=self.kind, programs=n1 - n0)
        return out


class ProgramRegistry:
    """Structural-signature -> compiled-callable map shared by every
    runner in the process (coordinator executor, worker task runners,
    EXPLAIN re-executions, rebuilt executors after SET SESSION).

    Bounded LRU: the registry would otherwise keep every jitted
    callable — and through it every compiled XLA executable — alive
    for the process lifetime, and XLA:CPU segfaults deterministically
    once the live-executable arena grows past a few thousand programs
    (the r5 TPC-DS finding; reproduced by the tier-1 suite the moment
    the registry went process-global).  Eviction only drops the
    registry's reference: runners holding an evicted Program keep
    using it; a future structural twin recompiles."""

    DEFAULT_MAX_CALLABLES = 256

    def __init__(self, max_callables: Optional[int] = None):
        import collections

        if max_callables is None:
            max_callables = int(os.environ.get(
                "PRESTO_TPU_PROGRAM_REGISTRY_CAP",
                self.DEFAULT_MAX_CALLABLES))
        self.max_callables = max_callables
        self._programs: "collections.OrderedDict[tuple, Program]" = \
            collections.OrderedDict()
        self._lock = named_lock("programs.ProgramRegistry._lock")
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_s = 0.0
        self.trace_events = 0

    def get(self, kind: str, sig, factory: Callable[[], Callable],
            jit: bool = True) -> Program:
        """The callable registered under (kind, signature), creating it
        via ``factory`` on first request.  ``jit`` is part of the key
        (a debug runner's eager callable must not shadow the compiled
        one)."""
        from presto_tpu.obs import METRICS

        key = (kind, bool(jit), ir_signature(sig))
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self.hits += 1
                METRICS.counter("xla.registry_hits").inc()
                self._programs.move_to_end(key)
                return prog
            self.misses += 1
            METRICS.counter("xla.registry_misses").inc()
            prog = Program(factory(), kind, jit, self)
            self._programs[key] = prog
            while len(self._programs) > self.max_callables:
                self._programs.popitem(last=False)
                self.evictions += 1
            return prog

    # -- metrics ------------------------------------------------------------
    def callable_count(self) -> int:
        with self._lock:
            return len(self._programs)

    def program_count(self) -> int:
        """Distinct compiled XLA programs across all registered
        callables (each shape signature of each callable is one)."""
        with self._lock:
            progs = list(self._programs.values())
        return sum(p._cache_size() for p in progs)

    def stats(self) -> Dict[str, Any]:
        out = {
            "callables": self.callable_count(),
            "programs": self.program_count(),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "compile_s": round(self.compile_s, 3),
            "trace_events": self.trace_events,
        }
        out.update(persistent_cache_stats())
        return out

    def clear(self) -> None:
        """Drop every registered callable (tests / executable-arena
        bounding; compiled executables additionally need
        ``jax.clear_caches()``)."""
        with self._lock:
            self._programs.clear()


_DEFAULT: Optional[ProgramRegistry] = None
_DEFAULT_LOCK = named_lock("programs._DEFAULT_LOCK")


def default_registry() -> ProgramRegistry:
    """The process-wide registry: every LocalRunner that isn't handed
    an explicit one shares it, so coordinator + worker runners + every
    rebuilt executor reuse one program space."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = ProgramRegistry()
        return _DEFAULT
