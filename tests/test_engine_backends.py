"""Backend equivalence: every registered backend == the python_exec oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.circuits import get_circuit
from repro.core.engine import available_backends, scan
from repro.core.scan import python_exec

CIRCUITS = ["ladner_fischer", "dissemination", "blelloch"]
SIZES = list(range(1, 18)) + [64, 100]


def _oracle(vals):
    """Sequential left-fold oracle (== python_exec on the sequential circuit,
    asserted once in test_oracle_is_python_exec)."""
    out = [vals[0]]
    for v in vals[1:]:
        out.append(out[-1] + v)
    return np.asarray(out)


def test_oracle_is_python_exec():
    n = 13
    vals = [float(i) for i in range(1, n + 1)]
    ys, _ = python_exec(lambda a, b: a + b, get_circuit("sequential", n), vals)
    np.testing.assert_allclose(ys, _oracle(vals))


def test_registry_exposes_all_backends():
    assert {"vector", "element", "blocked", "worksteal", "collective",
            "simulate", "pallas"} <= set(available_backends())


# ----------------------------------------------------------- array backends
@pytest.mark.parametrize("alg", CIRCUITS)
@pytest.mark.parametrize("n", SIZES)
def test_vector_matches_oracle(alg, n):
    x = np.linspace(0.5, 2.0, n)
    y = scan(lambda a, b: a + b, jnp.asarray(x), backend="vector", algorithm=alg)
    np.testing.assert_allclose(np.asarray(y), _oracle(list(x)), rtol=1e-6)


@pytest.mark.parametrize("alg", CIRCUITS)
@pytest.mark.parametrize("n", list(range(1, 18)) + [64])
def test_pallas_matches_oracle(alg, n):
    x = np.linspace(0.5, 2.0, n)
    y = scan(lambda a, b: a + b, jnp.asarray(x, jnp.float32), backend="pallas",
             algorithm=alg, interpret=True)
    np.testing.assert_allclose(np.asarray(y), _oracle(list(x)), rtol=1e-5)


def test_pallas_tiles_matches_oracle():
    n = 64
    x = np.linspace(0.1, 1.0, n)
    y = scan(jnp.maximum, jnp.asarray(x, jnp.float32), backend="pallas",
             num_blocks=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.maximum.accumulate(x),
                               rtol=1e-6)


@pytest.mark.parametrize("n", SIZES)
def test_blocked_matches_oracle(n):
    blocks = max(d for d in range(1, min(8, n) + 1) if n % d == 0)
    x = np.linspace(0.5, 2.0, n)
    y = scan(lambda a, b: a + b, jnp.asarray(x), backend="blocked",
             num_blocks=blocks)
    np.testing.assert_allclose(np.asarray(y), _oracle(list(x)), rtol=1e-6)


# --------------------------------------------------------- element backends
@pytest.mark.parametrize("backend", ["element", "simulate"])
@pytest.mark.parametrize("alg", CIRCUITS)
@pytest.mark.parametrize("n", SIZES)
def test_element_backends_match_oracle(backend, alg, n):
    vals = [float(i) * 0.5 for i in range(1, n + 1)]
    ys = scan(lambda a, b: a + b, vals, backend=backend, algorithm=alg)
    np.testing.assert_allclose(ys, _oracle(vals), rtol=1e-9)


@pytest.mark.parametrize("n", SIZES)
def test_worksteal_matches_oracle(n):
    vals = [float(i) * 0.5 for i in range(1, n + 1)]
    t = 4 if n >= 8 else (2 if n >= 4 else 1)
    ys = scan(lambda a, b: a + b, vals, backend="worksteal", num_threads=t)
    np.testing.assert_allclose(ys, _oracle(vals), rtol=1e-9)


# --------------------------------------------------- non-commutative operator
def _affine_op(a, b):
    return (a[0] * b[0], a[1] * b[0] + b[1])


def _affine_oracle(ms, cs):
    rm, rc = [ms[0]], [cs[0]]
    for m, c in zip(ms[1:], cs[1:]):
        rm.append(rm[-1] * m)
        rc.append(rc[-1] * m + c)
    return np.asarray(rm), np.asarray(rc)


@pytest.mark.parametrize("alg", CIRCUITS)
@pytest.mark.parametrize("n", [1, 2, 3, 7, 13, 17, 64])
def test_vector_noncommutative_pytree(alg, n):
    key = jax.random.PRNGKey(0)
    m = jax.random.uniform(key, (n,), minval=0.6, maxval=1.1)
    c = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.5
    ym, yc = scan(_affine_op, (m, c), backend="vector", algorithm=alg)
    rm, rc = _affine_oracle(np.asarray(m), np.asarray(c))
    np.testing.assert_allclose(np.asarray(ym), rm, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yc), rc, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("backend", ["element", "worksteal", "simulate"])
def test_element_noncommutative(backend):
    n = 33
    rng = np.random.default_rng(7)
    items = [(float(m), float(c))
             for m, c in zip(rng.uniform(0.7, 1.1, n), rng.normal(0, 0.5, n))]
    kw = {"num_threads": 4} if backend == "worksteal" else {}
    ys = scan(_affine_op, items, backend=backend, **kw)
    rm, rc = _affine_oracle([i[0] for i in items], [i[1] for i in items])
    np.testing.assert_allclose([y[0] for y in ys], rm, rtol=1e-9)
    np.testing.assert_allclose([y[1] for y in ys], rc, rtol=1e-9)


# ------------------------------------------------------- collective (8 dev)
COLLECTIVE_SNIPPET = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from functools import partial
from repro.core.engine import scan

devs = np.array(jax.devices())
mesh = Mesh(devs, ("x",))
x = jnp.arange(1.0, 9.0)
for alg in ["dissemination", "ladner_fischer", "brent_kung", "sklansky"]:
    f = shard_map(partial(scan, lambda a, b: a + b, backend="collective",
                          axis_name="x", axis_size=8, algorithm=alg),
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(f(x)), np.cumsum(np.arange(1, 9)))
print("COLLECTIVE_ENGINE_OK")
"""


@pytest.mark.slow
def test_collective_backend_8dev(subproc):
    out = subproc(COLLECTIVE_SNIPPET, devices=8)
    assert "COLLECTIVE_ENGINE_OK" in out
