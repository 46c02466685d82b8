"""Distributed hierarchical scan demo on 8 virtual devices (2 pods x 4 chips):
the paper's §4.1/§4.2 running as shard_map collectives, plus the in-model
sequence-parallel SSD scan.

  python examples/distributed_scan_demo.py        # sets its own XLA_FLAGS
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core.deformation import compose_batched  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    collective_scan,
    distributed_blocked_scan,
    hierarchical_collective_scan,
)
from repro.runtime.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
devs = np.array(jax.devices())
print(f"devices: {len(devs)} (virtual pod layout 2x4)")

# --- flat collective scan: one deformation per device ----------------------
mesh = Mesh(devs, ("chip",))
defs = {
    "angle": jnp.linspace(-0.02, 0.02, 8),
    "shift": jnp.stack([jnp.linspace(0, 7, 8), jnp.linspace(7, 0, 8)], -1),
}
for alg in ["dissemination", "ladner_fischer"]:
    f = shard_map(
        partial(collective_scan, compose_batched, axis_name="chip",
                algorithm=alg, axis_size=8),
        mesh=mesh, in_specs=P("chip"), out_specs=P("chip"),
    )
    y = f(defs)
    print(f"flat {alg:16s}: total shift = {np.asarray(y['shift'][-1])}")

# --- hierarchical (pod, chip): global phase only between pods --------------
mesh2 = Mesh(devs.reshape(2, 4), ("pod", "chip"))
f = shard_map(
    partial(hierarchical_collective_scan, compose_batched,
            axis_names=("pod", "chip"), axis_sizes=(2, 4)),
    mesh=mesh2, in_specs=P(("pod", "chip")), out_specs=P(("pod", "chip")),
)
y = f(defs)
print(f"hierarchical (2 pods x 4): total shift = {np.asarray(y['shift'][-1])}")

# --- N >> P: local-global-local (paper Fig. 6) ------------------------------
n = 512
big = {
    "angle": jnp.zeros((n,)),
    "shift": jnp.ones((n, 2)) * 0.1,
}
f = shard_map(
    partial(distributed_blocked_scan, compose_batched,
            axis_names=("pod", "chip"), strategy="reduce_then_scan",
            axis_sizes=(2, 4)),
    mesh=mesh2, in_specs=P(("pod", "chip")), out_specs=P(("pod", "chip")),
)
y = f(big)
print(f"blocked reduce-then-scan over N={n}: shift[-1] = "
      f"{np.asarray(y['shift'][-1])} (expect [51.2, 51.2])")

# --- the same machinery inside a model: sequence-parallel SSD scan ---------
from repro.kernels import ops, ref  # noqa: E402

b, h, l, dk, dv = 1, 2, 512, 16, 16
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 4)
q = jax.random.normal(ks[0], (b, h, l, dk)) * 0.3
k = jax.random.normal(ks[1], (b, h, l, dk)) * 0.3
v = jax.random.normal(ks[2], (b, h, l, dv)) * 0.5
la = -jax.nn.softplus(jax.random.normal(ks[3], (b, h, l)))

ref_y = jax.vmap(jax.vmap(ref.ssm_scan_reference))(q, k, v, la)


def seq_parallel_ssd(q, k, v, la):
    return ops.ssd_scan(q, k, v, la, chunk=32, backend="xla",
                        axis_names=("pod", "chip"), axis_sizes=(2, 4))


f = shard_map(
    seq_parallel_ssd, mesh=mesh2,
    in_specs=(P(None, None, ("pod", "chip"), None),) * 3
    + (P(None, None, ("pod", "chip")),),
    out_specs=P(None, None, ("pod", "chip"), None),
)
y = f(q, k, v, la)
err = np.abs(np.asarray(y) - np.asarray(ref_y)).max()
print(f"sequence-parallel SSD scan over (pod, chip): max err vs recurrence "
      f"oracle = {err:.2e}")
assert err < 1e-3
print("OK")
