"""Cost-model dispatcher, plan caching through scan(), API edge cases."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (
    EXPENSIVE_OP_COST,
    dispatch,
    measure_op_cost,
    op_concurrency_from,
    plan_cache,
    register_backend,
    scan,
)
from repro.core.scan import prefix_scan


# ------------------------------------------------------------------ dispatch
def test_cheap_array_op_goes_vector():
    d = dispatch(256, domain="array")
    assert d.backend == "vector"
    assert d.algorithm == "ladner_fischer"  # depth-optimal for cheap ops


def test_large_cheap_array_goes_blocked():
    d = dispatch(1 << 20, domain="array", workers=4)
    assert d.backend == "blocked"
    assert d.strategy == "reduce_then_scan"
    assert d.num_blocks and (1 << 20) % d.num_blocks == 0


def test_expensive_array_op_goes_blocked_reduce_then_scan():
    """The paper's rule: when op cost dominates, pick reduce-then-scan."""
    d = dispatch(64, domain="array", op_cost=1.0, workers=4)
    assert d.backend == "blocked"
    assert d.strategy == "reduce_then_scan"


def test_expensive_element_op_goes_worksteal():
    d = dispatch(64, domain="element", op_cost=10.0, workers=4)
    assert d.backend == "worksteal"
    assert d.num_threads == 4
    assert d.algorithm == "dissemination"  # paper §4.3 phase-2 choice


def test_cheap_element_op_stays_element():
    d = dispatch(64, domain="element", op_cost=1e-6, workers=4)
    assert d.backend == "element"


def test_single_worker_never_worksteals():
    d = dispatch(64, domain="element", op_cost=10.0, workers=1)
    assert d.backend == "element"


class _Reports:
    """An operator that reports ``op_concurrency`` as given."""

    def __init__(self, concurrency):
        self.op_concurrency = concurrency


@pytest.mark.parametrize("op,c", [
    (_Reports(1), 1),
    (_Reports(lambda: 1), 1),
    (_Reports(2), 2),
    (_Reports(lambda: 4), 4),
    (object(), None),
], ids=["int1", "callable1", "int2", "callable4", "absent"])
def test_op_concurrency_caps_element_workers(op, c):
    """The element-domain worker budget stops at the operator's concurrency:
    at 1 no parallel phase applies (the work-optimal chain), above it the
    parallel backends run with at most that many threads, and an operator
    that says nothing gets exactly the decision it got before."""
    assert op_concurrency_from(op) == c
    base = dict(domain="element", op_cost=0.1, workers=64)
    d = dispatch(8, **base, op_concurrency=op_concurrency_from(op))
    if c is None:
        assert d == dispatch(8, **base)
        assert d.backend == "worksteal"
    elif c == 1:
        assert (d.backend, d.algorithm) == ("element", "sequential")
    else:
        assert d.backend in ("worksteal", "hierarchical")
        assert d.num_threads <= c


@pytest.mark.parametrize("n,imbalance,backend,cross", [
    (32, None, "hierarchical", True),
    (32, 1.2, "hierarchical", False),
    (31, 3.0, "hierarchical", True),
    (7, None, "worksteal", None),
], ids=["unobserved", "balanced", "imbalanced", "too_short"])
def test_several_devices_get_one_worker_each(n, imbalance, backend, cross):
    """An expensive operator whose data lies on four devices runs the
    hierarchical scan with one segment and one worker per device, stealing
    across segments unless the observed imbalance is low; fewer than two
    elements a device fall back to flat stealing."""
    d = dispatch(n, domain="element", op_cost=0.1, workers=30,
                 op_imbalance=imbalance, op_concurrency=4)
    assert d.backend == backend
    if backend == "hierarchical":
        assert (d.num_segments, d.num_threads, d.cross_steal) == (4, 1, cross)


def test_one_executor_operator_runs_the_seeded_chain():
    """Through ``scan``: an expensive operator whose applications cannot run
    at once folds a seeded suffix in one application per element."""
    calls = []

    class OneDevice:
        op_cost_estimate = 1.0
        op_concurrency = 1

        def __call__(self, a, b):
            calls.append((a, b))
            return a + b

    vals = [float(i) for i in range(1, 9)]
    ys = scan(OneDevice(), vals, seed=10.0, workers=8)
    np.testing.assert_allclose(ys, 10.0 + np.cumsum(vals))
    assert len(calls) == len(vals)


def test_measure_op_cost_orders_regimes():
    fast = measure_op_cost(lambda a, b: a + b, [1.0, 2.0, 3.0])
    slow = measure_op_cost(
        lambda a, b: (time.sleep(0.01), a + b)[1], [1.0, 2.0, 3.0]
    )
    assert 0 <= fast < slow
    assert slow >= EXPENSIVE_OP_COST


def test_scan_measure_routes_expensive_op():
    """End-to-end: a slow operator measured at scan time -> worksteal."""

    def slow_add(a, b):
        time.sleep(0.006)
        return a + b

    vals = [float(i) for i in range(1, 17)]
    ys = scan(slow_add, vals, measure=True, workers=2)
    np.testing.assert_allclose(ys, np.cumsum(vals))


# ------------------------------------------------------------------- caching
def test_scan_hits_plan_cache_on_second_call():
    plan_cache.clear()
    x = jnp.arange(1.0, 42.0)
    y1 = scan(lambda a, b: a + b, x, backend="vector")
    s = plan_cache.stats()
    y2 = scan(lambda a, b: a + b, x, backend="vector")
    s2 = plan_cache.stats()
    assert s2["hits"] > s["hits"] and s2["misses"] == s["misses"]
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


# ------------------------------------------------------------------ API edge
def test_scan_trivial_sizes():
    assert scan(lambda a, b: a + b, []) == []
    assert scan(lambda a, b: a + b, [5.0]) == [5.0]
    x = jnp.asarray([3.0])
    np.testing.assert_allclose(np.asarray(scan(lambda a, b: a + b, x)), [3.0])


def test_scan_matches_prefix_scan_wrapper():
    x = jnp.arange(1.0, 34.0)
    a = prefix_scan(jnp.maximum, x, algorithm="brent_kung")
    b = scan(jnp.maximum, x, backend="vector", algorithm="brent_kung")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_where_mask_skips_elements():
    x = jnp.arange(1.0, 9.0)
    where = [True, True, False, True, True, False, True, True]
    y = np.asarray(scan(lambda a, b: a + b, x, where=where))
    expect = [1, 3, None, 7, 12, None, 19, 27]  # masked -> identity
    for i, e in enumerate(expect):
        if e is not None:
            assert y[i] == e, (i, y[i], e)


def test_where_mask_rejects_decomposition_backends():
    """blocked/worksteal/pallas-tiles cannot honor masks: explicit -> raise."""
    x = jnp.arange(1.0, 17.0)
    where = [True] * 8 + [False] * 8
    for kw in [dict(backend="blocked", num_blocks=4),
               dict(backend="pallas", num_blocks=4)]:
        with pytest.raises(NotImplementedError, match="where masks"):
            scan(lambda a, b: a + b, x, where=where, **kw)
    with pytest.raises(NotImplementedError, match="where masks"):
        scan(lambda a, b: a + b, list(range(16)), where=where,
             backend="worksteal", num_threads=2)


def test_where_mask_survives_auto_dispatch(monkeypatch):
    """When the dispatcher would pick 'blocked', a mask must force the flat
    executor, not be silently dropped."""
    from repro.core.engine import cost

    monkeypatch.setattr(cost, "BLOCKED_MIN_N", 64)
    assert dispatch(64, domain="array").backend == "blocked"  # sanity
    n = 64
    x = jnp.ones(n)
    where = [i < n // 2 for i in range(n)]
    y = np.asarray(scan(lambda a, b: a + b, x, where=where))
    assert y[n // 2 - 1] == n // 2
    assert y[-1] == n // 2  # masked second half contributes nothing


def test_where_mask_rejects_blelloch():
    with pytest.raises(NotImplementedError):
        scan(lambda a, b: a + b, jnp.arange(4.0), algorithm="blelloch",
             where=[True, False, True, True])


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown scan backend"):
        scan(lambda a, b: a + b, jnp.arange(4.0), backend="nope")


def test_duplicate_backend_registration_raises():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("vector", lambda *a, **k: None)
