"""Persistent compile cache: executable cache, plan store, and the
compile-time/telemetry split that keeps XLA tracing out of cost EMAs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine.plan import get_plan, plan_cache
from repro.core.engine.telemetry import OpTelemetry
from repro.runtime.compile_cache import (
    CompileCache,
    PlanStore,
    get_plan_store,
    reset_compile_cache,
    set_cache_dir,
)


@pytest.fixture
def clean_cache_state():
    """Detach the global plan store / executable cache around a test and
    restore jax's persistent-cache flag, so cache-dir tests never leak
    into the rest of the suite."""
    yield
    reset_compile_cache()
    try:
        jax.config.update("jax_compilation_cache_dir", None)
    except Exception:
        pass


# ------------------------------------------------------- executable cache


def test_compile_cache_hit_miss_and_counters():
    cache = CompileCache()
    builds = []

    def build():
        builds.append(1)
        return lambda x: x * 2.0

    x = jnp.arange(4.0)
    counters = {"hits": 0, "misses": 0, "compile_s": 0.0}
    f1 = cache.get_compiled("k", build, lower_args=(x,), counters=counters)
    f2 = cache.get_compiled("k", build, lower_args=(x,), counters=counters)
    assert f1 is f2 and len(builds) == 1
    assert counters["hits"] == 1 and counters["misses"] == 1
    assert counters["compile_s"] > 0
    np.testing.assert_array_equal(np.asarray(f1(x)), np.arange(4.0) * 2)
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["size"] == 1
    # AOT: the cached object is a compiled executable, not the raw callable.
    assert not hasattr(f1, "lower")
    # Distinct keys compile separately.
    cache.get_compiled("k2", build, lower_args=(x,))
    assert len(builds) == 2
    cache.clear()
    assert cache.stats() == {"hits": 0, "misses": 0, "compile_s": 0.0,
                             "size": 0}


def test_compile_cache_without_lower_args_caches_callable():
    cache = CompileCache()
    fn = cache.get_compiled("k", lambda: (lambda x: x + 1))
    assert fn(1) == 2
    assert cache.get_compiled("k", lambda: None) is fn


# ------------------------------------------------------------- plan store


def test_plan_store_roundtrip(tmp_path):
    store = PlanStore(str(tmp_path))
    plan = get_plan("ladner_fischer", 16)
    key = ("ladner_fischer", 16, (False,) * 16)
    assert store.store(key, plan)
    loaded = store.load(key)
    assert loaded is not None
    assert loaded.circuit == plan.circuit
    assert loaded.rounds == plan.rounds
    assert loaded.scratch == {}          # device memos are stripped
    assert store.load(("missing", 8, ())) is None


def test_plan_store_tolerates_corruption(tmp_path):
    store = PlanStore(str(tmp_path))
    plan = get_plan("ladner_fischer", 8)
    key = ("ladner_fischer", 8, (False,) * 8)
    store.store(key, plan)
    with open(store._path(key), "wb") as f:
        f.write(b"not a pickle")
    assert store.load(key) is None


def test_get_plan_consults_persistent_store(tmp_path, clean_cache_state):
    set_cache_dir(str(tmp_path))
    store = get_plan_store()
    assert store is not None
    plan_cache.clear()
    plan = get_plan("brent_kung", 32)          # lowers fresh, persists
    assert store.stores >= 1
    plan_cache.clear()                          # simulate a fresh process
    loads_before = store.loads
    again = get_plan("brent_kung", 32)
    assert store.loads == loads_before + 1
    assert again.circuit == plan.circuit and again.rounds == plan.rounds
    # And the loaded plan executes: scan through it bit-exactly.
    from repro.core.engine import scan

    x = jnp.asarray(np.arange(32.0), jnp.float32)
    y = scan(lambda a, b: a + b, x, backend="vector", algorithm="brent_kung")
    np.testing.assert_array_equal(np.asarray(y), np.cumsum(np.arange(32.0)))


# ----------------------------------------------- telemetry compile split


def test_telemetry_compile_split():
    tel = OpTelemetry(name="t")
    tel.record(5.0, compile=True)
    assert tel.calls == 0 and tel.estimate() is None
    assert tel.compile_calls == 1 and tel.compile_time == 5.0
    tel.record(0.1)
    assert tel.calls == 1
    assert abs(tel.estimate() - 0.1) < 1e-12   # EMA untouched by compile
    s = tel.summary()
    assert s["compile_calls"] == 1 and s["compile_s"] == 5.0
    tel.reset()
    assert tel.compile_calls == 0 and tel.compile_time == 0.0


def test_operator_first_call_classified_as_compile():
    from repro.core.registration import (
        RegElement,
        RegistrationOperator,
        SeriesRegistrar,
    )

    RegistrationOperator._reset_compile_tracking()
    frames = jnp.zeros((4, 8, 8), jnp.float32)
    reg = SeriesRegistrar(frames, refine=False)
    op = RegistrationOperator(reg, name="t_cold")
    e = lambda i: RegElement(
        {"angle": jnp.zeros(()), "shift": jnp.zeros((2,))}, i, i + 1
    )
    op(e(0), e(1))
    assert op.telemetry.compile_calls == 1 and op.telemetry.calls == 0
    op(e(1), e(2))
    assert op.telemetry.compile_calls == 1 and op.telemetry.calls == 1
    # Compile-dominated samples never become per-element cost observations.
    assert list(op._elem_obs) != [] and 0 not in op._elem_obs
    # A second operator over the same signature starts warm.
    op2 = RegistrationOperator(SeriesRegistrar(frames, refine=False),
                               name="t_warm")
    op2(e(0), e(1))
    assert op2.telemetry.compile_calls == 0 and op2.telemetry.calls == 1


# -------------------------------------------------------- service wiring


def test_series_session_warm_start(tmp_path, clean_cache_state):
    from repro.service import RegisterSeriesConfig, open_series

    frames = jnp.asarray(
        np.random.default_rng(0).standard_normal((8, 16, 16)), jnp.float32
    )
    cfg = RegisterSeriesConfig(refine=False, telemetry_name="t_cc_cold")

    def run(tag):
        with open_series(
            RegisterSeriesConfig(refine=False, telemetry_name=tag),
            compile_cache_dir=str(tmp_path),
        ) as s:
            s.feed(frames[:4])
            s.feed(frames[4:])
            return s.result()

    cold = run("t_cc_cold")
    assert cold.compile_cache["misses"] >= 1
    assert cold.timings["compile"] > 0
    # Compile seconds were moved out of preprocess, not double counted.
    assert cold.timings["preprocess"] >= 0
    warm = run("t_cc_warm")
    assert warm.compile_cache["hits"] >= 1
    assert warm.compile_cache["misses"] == 0
    assert warm.timings["compile"] == 0
    np.testing.assert_allclose(
        np.asarray(warm.deformations["shift"]),
        np.asarray(cold.deformations["shift"]),
        atol=1e-6,
    )
    assert "compile cache:" in warm.report()


# ------------------------------------------------ persistent cache placement


def test_persistent_cache_placement(subproc, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` is the cache's one directory: compiled
    programs land there, and a session's ``compile_cache_dir`` moves only
    the plan store.  Unset, the cache is ``.jax_cache/`` at the checkout
    root.  A child process: JAX fixes its cache once per process."""
    import os

    env_dir, plans = tmp_path / "env", tmp_path / "plans"
    out = subproc(f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(env_dir)!r}
import jax, jax.numpy as jnp
from repro.runtime import compile_cache as cc
print(cc.enable_persistent_cache())
cc.set_cache_dir({str(plans)!r})
jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(8)).block_until_ready()
del os.environ["JAX_COMPILATION_CACHE_DIR"]
print(cc.DEFAULT_CACHE_DIR)
""", devices=1)
    placed, default = out.split()
    assert placed == str(env_dir)
    assert any(p.name.startswith("jit_") for p in env_dir.iterdir())
    assert not any(p.name.startswith("jit_") for p in plans.iterdir())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert default == os.path.join(repo, ".jax_cache")
