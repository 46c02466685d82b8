"""Quickstart: the work-stealing prefix scan library in 5 minutes.

  PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.circuits import analyze, get_circuit
from repro.core.deformation import compose_batched
from repro.core.engine import available_backends, cache_stats, dispatch, scan
from repro.core.scan import blocked_scan, prefix_scan
from repro.core.work_stealing import static_reduce, stealing_reduce
from repro.runtime.compile_cache import enable_persistent_cache

enable_persistent_cache()

# ---------------------------------------------------------------- circuits
print("== Prefix circuits (paper Table 1) ==")
for name in ["sequential", "dissemination", "blelloch", "ladner_fischer"]:
    st = analyze(get_circuit(name, 256))
    print(f"  {name:16s} N=256: work={st.work:5d} depth={st.depth:3d} "
          f"rounds={st.rounds}")

# ------------------------------------------------- scans on rigid transforms
print("\n== Scanning the registration operator (rigid deformations) ==")
key = jax.random.PRNGKey(0)
n = 64
defs = {
    "angle": jax.random.normal(key, (n,)) * 0.02,
    "shift": jax.random.normal(key, (n, 2)) * 2.0,
}
for alg in ["ladner_fischer", "dissemination", "blelloch"]:
    y = prefix_scan(compose_batched, defs, algorithm=alg)
    print(f"  {alg:16s} cumulative shift[-1] = {np.asarray(y['shift'][-1])}")

# local-global-local (paper 4.1) on one device
y = blocked_scan(compose_batched, defs, num_blocks=8,
                 strategy="reduce_then_scan", algorithm="ladner_fischer")
print(f"  blocked (reduce-then-scan)      = {np.asarray(y['shift'][-1])}")

# ------------------------------------------------------- the unified engine
print("\n== Unified scan engine (circuit -> plan -> backend) ==")
print(f"  registered backends: {available_backends()}")
# One entry point; the cost model picks backend + circuit + block size.
y = scan(compose_batched, defs)
print(f"  scan(op, xs) auto               = {np.asarray(y['shift'][-1])}")
d = dispatch(len(defs['angle']), domain='array', op_cost=10.0)
print(f"  10 s/op operator would dispatch to: {d.backend} "
      f"({d.strategy}, {d.reason})")
# Explicit backends all consume the same cached plans:
y = scan(jnp.add, jnp.arange(1.0, 65.0), backend="pallas", num_blocks=8)
print(f"  pallas tile-scan cumsum[-1]     = {float(y[-1]):.0f}")
y = scan(lambda a, b: a + b, list(range(1, 65)), backend="worksteal",
         num_threads=3)
print(f"  worksteal cumsum[-1]            = {y[-1]}")
print(f"  plan cache: {cache_stats()['plan']}")

# ------------------------------------------------------------ work stealing
print("\n== Work stealing on an imbalanced operator (paper Alg. 1) ==")
rng = np.random.default_rng(1410)
delays = rng.exponential(0.002, size=96)


def slow_op(a, b):
    time.sleep(delays[b[1] % 96])
    return (a[0] + b[0], b[1])


items = [(1, i) for i in range(96)]
t0 = time.time()
_, st_static = static_reduce(slow_op, items, 3)
t_static = time.time() - t0
t0 = time.time()
_, st_steal = stealing_reduce(slow_op, items, 3)
t_steal = time.time() - t0
print(f"  static : {t_static * 1e3:6.1f} ms  imbalance={st_static.imbalance():.2f}")
print(f"  stealing: {t_steal * 1e3:6.1f} ms  imbalance={st_steal.imbalance():.2f}  "
      f"boundaries={st_steal.boundaries}")
