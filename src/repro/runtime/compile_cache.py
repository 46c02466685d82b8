"""Persistent compilation cache: warm-start the registration hot path.

A cold ``register_series`` pays seconds of XLA compilation before the first
pair registers — in the paper's streaming setting (a new 4,096-frame series
every ten seconds) that latency lands on *every* process start.  Three layers
remove it:

1. **In-process executable cache** (:class:`CompileCache`): ahead-of-time
   compiled executables keyed by ``(fn role, shapes, dtype, config)``.  The
   session's batched function-A launcher is compiled once per
   (chunk length, frame shape, registration config) signature and reused
   across feeds, sessions and series; hit/miss/compile-second counters are
   surfaced per session (``SeriesResult.report()``).
2. **JAX persistent cache** (:func:`enable_persistent_cache`): XLA
   executables survive process restarts.  ``JAX_COMPILATION_CACHE_DIR``,
   when set, is the cache's one directory; otherwise it is a fixed
   ``.jax_cache/`` at the checkout root (the path is part of the cache
   key, so it must not move between runs).  Scripts call it at start.
3. **Plan store** (:class:`PlanStore`): lowered
   :class:`~repro.core.engine.plan.ExecutionPlan` schedules pickled next to
   the XLA cache.  ``get_plan`` consults the store on an LRU miss, so a
   fresh process skips the symbolic circuit trace for every schedule any
   previous run has lowered (backend ``scratch`` memos are stripped before
   pickling — they hold device arrays and are rebuilt lazily).

Everything here is dependency-free and failure-tolerant: a broken cache dir
never breaks a scan, it only forfeits the warm start.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax

__all__ = [
    "CACHE_DIR_ENV",
    "CompileCache",
    "DEFAULT_CACHE_DIR",
    "PlanStore",
    "enable_persistent_cache",
    "get_compile_cache",
    "get_plan_store",
    "reset_compile_cache",
    "set_cache_dir",
]


class CompileCache:
    """Thread-safe cache of ahead-of-time compiled executables.

    ``get_compiled(key, build, lower_args=...)`` returns the cached
    executable for ``key``; on a miss it calls ``build()`` for the function,
    AOT-compiles it against ``lower_args`` (``jax.jit(fn).lower(*args)
    .compile()``) and caches the result.  Without ``lower_args`` the built
    callable itself is cached (compilation then happens lazily on first
    call, outside the cache's compile-second accounting).

    ``counters`` lets a caller (a series session) accumulate its own view
    of hits/misses/compile seconds on top of the process-wide totals.
    """

    def __init__(self):
        self._fns: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0

    def get_compiled(
        self,
        key: Any,
        build: Callable[[], Callable],
        *,
        lower_args: Optional[tuple] = None,
        counters: Optional[Dict[str, float]] = None,
    ):
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                if counters is not None:
                    counters["hits"] = counters.get("hits", 0) + 1
                return fn
        # Compile outside the lock: a long XLA compile must not serialize
        # unrelated sessions.  A racing duplicate compile is wasted work,
        # not an error — last writer wins on identical executables.
        t0 = time.perf_counter()
        fn = build()
        if lower_args is not None:
            jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
            fn = jitted.lower(*lower_args).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self.misses += 1
            self.compile_seconds += dt
            self._fns[key] = fn
        if counters is not None:
            counters["misses"] = counters.get("misses", 0) + 1
            counters["compile_s"] = counters.get("compile_s", 0.0) + dt
        return fn

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "compile_s": self.compile_seconds,
                "size": len(self._fns),
            }

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = 0
            self.misses = 0
            self.compile_seconds = 0.0


class PlanStore:
    """Pickle-per-key persistent store for lowered execution plans.

    Keys are the ``get_plan`` cache keys (name, n, mask tuple); each plan
    lives in its own file named by the key's sha1, so concurrent processes
    never contend on one index file.  Writes go through a same-directory
    temp file + ``os.replace`` (atomic on POSIX); loads tolerate missing,
    truncated or version-incompatible files by returning None.
    """

    def __init__(self, directory: str):
        self.directory = os.path.join(directory, "plans")
        os.makedirs(self.directory, exist_ok=True)
        # The hit counters are read by cache stats while worker threads
        # load/store plans concurrently; `n += 1` is not atomic.
        self._lock = threading.Lock()
        self.loads = 0
        self.stores = 0

    def _path(self, key: Any) -> str:
        digest = hashlib.sha1(repr(key).encode()).hexdigest()
        return os.path.join(self.directory, f"{digest}.pkl")

    def load(self, key: Any):
        try:
            with open(self._path(key), "rb") as f:
                plan = pickle.load(f)
        except (OSError, pickle.UnpicklingError, EOFError, AttributeError,
                ImportError, IndexError):
            return None
        with self._lock:
            self.loads += 1
        return plan

    def store(self, key: Any, plan) -> bool:
        # Backend scratch memos hold device arrays (jnp index tables) —
        # unpicklable and rebuilt lazily, so persist the plan without them.
        plan = dataclasses.replace(plan, scratch={})
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                pickle.dump(plan, f)
            os.replace(tmp, path)
        except (OSError, pickle.PicklingError, TypeError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        with self._lock:
            self.stores += 1
        return True


_cache = CompileCache()
_plan_store: Optional[PlanStore] = None
_state_lock = threading.Lock()


def get_compile_cache() -> CompileCache:
    """The process-wide executable cache."""
    return _cache


def get_plan_store() -> Optional[PlanStore]:
    """The persistent plan store, or None until ``set_cache_dir`` ran."""
    return _plan_store


def reset_compile_cache() -> None:
    """Drop all in-process cached executables and detach the plan store
    (tests; the on-disk store is left intact)."""
    global _plan_store
    with _state_lock:
        _cache.clear()
        _plan_store = None


#: Environment variable that, when set, names the persistent cache's only
#: directory (JAX reads the same variable).
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The persistent cache's directory when ``CACHE_DIR_ENV`` is unset.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def _point_jax_cache(path: str) -> None:
    jax.config.update("jax_compilation_cache_dir", path)
    # Default thresholds skip sub-second compiles — exactly the small
    # registration kernels this cache exists for.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` if set, else :data:`DEFAULT_CACHE_DIR`.
    Call before the first compile: JAX decides once per process.
    """
    path = os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    _point_jax_cache(path)
    return path


def set_cache_dir(path: str) -> None:
    """Persist lowered plans under ``path``, and XLA executables there too
    unless ``$JAX_COMPILATION_CACHE_DIR`` already places them."""
    global _plan_store
    path = os.fspath(path)
    os.makedirs(path, exist_ok=True)
    with _state_lock:
        _plan_store = PlanStore(path)
    if not os.environ.get(CACHE_DIR_ENV):
        _point_jax_cache(path)
