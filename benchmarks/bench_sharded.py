"""Strong scaling of the sharded multi-device backend (docs/ARCHITECTURE.md
"Sharded execution").

One long series (n = 4096 affine composes over width-192 rows) executed as a
single scan, at 1 / 4 / 8 virtual CPU devices.  Each device count runs in its
own subprocess so ``--xla_force_host_platform_device_count`` is set before jax
imports.  On a host with an accelerator the suite stops with a message: a
child cannot reach a chip the parent holds, and CPU numbers must not pass
for the chip's; the single-device row uses the ``vector`` backend (the dispatcher's
honest single-device choice for a cheap batchable op), the multi-device rows
the ``sharded`` backend (what the dispatcher picks at >= 4 devices and
n >= 1024).

The container pins every virtual device to the same cores, so wall-clock
speedup here is *algorithmic*: blocked reduce-then-scan over shards does
~2N op applications against the vector backend's O(N log N) gather circuit.
Acceptance (gated via compare_baseline.py against the committed
BENCH_sharded_ci.json):

* ``sharded_speedup_8dev`` >= 1.5x the single-device wall time (hard floor;
  committed baseline ratio is hand-clamped below measured ~1.9-2.1x so
  RATIO_SLACK keeps margin on slow runners);
* the executed cross-shard phase-2 round count equals ceil(log2 p) — the
  Traeff exscan schedule — and stays <= the inclusive hierarchical
  baseline's rounds + shift (``rounds_le_hier``);
* the simulator's predicted phase-2 round count equals the executed one
  (``sim_rounds_match``).
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")

N = 4096
W = 192
DEVICE_COUNTS = (1, 4, 8)

# Runs in a fresh interpreter per device count: XLA_FLAGS must be final
# before jax first imports, and jax never re-reads it.
_CHILD = r"""
import json, os, sys, time

dev, n, w, reps = (int(a) for a in sys.argv[1:5])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={dev}"

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import scan, sharded

assert jax.device_count() == dev, (jax.device_count(), dev)

rng = np.random.default_rng(0)
# Affine composes (m, c): mostly-identity slopes with sparse 1.0001 bumps
# keep the running products bounded over 4096 steps.
m = jnp.asarray(np.where(rng.random((n, w)) < 0.01, 1.0001, 1.0)
                .astype(np.float32))
c = jnp.asarray(rng.standard_normal((n, w)).astype(np.float32))
aff = lambda a, b: (a[0] * b[0], a[1] * b[0] + b[1])

backend = "vector" if dev == 1 else "sharded"


def once():
    ym, yc = scan(aff, (m, c), backend=backend)
    ym.block_until_ready()
    yc.block_until_ready()


once()
once()  # second warmup: callback plumbing + caches settled
ts = []
for _ in range(reps):
    t0 = time.perf_counter()
    once()
    ts.append(time.perf_counter() - t0)

out = {"devices": dev, "wall_s": float(np.median(ts))}
if dev > 1:
    st = sharded.last_stats
    assert st is not None and st.devices == dev, st
    out["phase2_rounds"] = int(st.phase2_rounds)
    out["phase2_algorithm"] = st.phase2_algorithm
    out["cross_steals"] = int(st.cross_steals)
print("RESULT " + json.dumps(out))
"""


def _measure(dev: int, reps: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(dev), str(N), str(W), str(reps)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_sharded child (devices={dev}) failed:\n"
            f"{proc.stdout}\n{proc.stderr}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line from child (devices={dev})")


def run(*, smoke: bool = False) -> list:
    import jax

    from repro.core.circuits import get_circuit
    from repro.core.simulator import constant_costs, simulate_distributed_scan

    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            "bench_sharded simulates 1/4/8 devices on the CPU, one child "
            f"process per device count; this host has a {platform}, so its "
            "numbers would not be the devices' own.  The multi-chip path "
            "runs on the real devices with `python chip_smoke.py --chips 4`."
        )
    reps = 5 if smoke else 11
    rows = []
    base = _measure(1, reps)
    us1 = base["wall_s"] * 1e6
    rows.append((f"sharded_1dev_n{N}", us1, "backend=vector"))

    for dev in DEVICE_COUNTS[1:]:
        r = _measure(dev, reps)
        us = r["wall_s"] * 1e6
        speedup = us1 / us
        rounds = r["phase2_rounds"]
        assert r["phase2_algorithm"] == "exscan", r
        assert rounds == math.ceil(math.log2(dev)), r
        # Inclusive hierarchical schedule pays the plan's rounds plus the
        # exclusive shift a distributed lowering needs.
        hier_rounds = get_circuit("ladner_fischer", dev).num_rounds() + 1
        sim = simulate_distributed_scan(
            constant_costs(N), ranks=dev, algorithm="exscan")
        derived = (
            f"sharded_speedup_{dev}dev={speedup:.2f}x"
            f";phase2_rounds={rounds}"
            f";rounds_le_hier={rounds <= hier_rounds}"
            f";sim_rounds_match={sim.phase2_rounds == rounds}"
            f";cross_steals={r['cross_steals']}"
        )
        rows.append((f"sharded_{dev}dev_n{N}", us, derived))
    return rows


if __name__ == "__main__":
    try:
        from _cli import bench_cli          # script: python benchmarks/...
    except ImportError:
        from ._cli import bench_cli         # package: benchmarks.run
    bench_cli("sharded", run)
