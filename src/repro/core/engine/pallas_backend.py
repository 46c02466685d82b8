"""Pallas tile-scan backend: plans executed as fused kernels.

Two modes, selected by the width of the plan handed in (the same convention
as the ``blocked`` backend):

* ``plan.n == len(xs)``  → **rounds mode**: every plan round runs as one
  fused gather–combine–scatter kernel (one-hot MXU matmuls around a single
  vectorized operator application — see ``kernels/tile_scan.py``).
* ``plan.n <  len(xs)``  → **tiles mode**: the paper's local–global–local
  decomposition with both local phases fused into single kernel launches;
  the plan drives the tiny global phase over ``plan.n`` tile totals.

Restricted to single-leaf float arrays and operators that vectorize over the
leading axis (the "common low-compute operators" regime of the paper §4.1).
``interpret=None`` compiles the kernels on a TPU and interprets them
anywhere else (``kernels/_tiling.resolve_interpret``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backends import (
    exec_vector,
    lowered_cache,
    plan_key,
    register_backend,
)
from .plan import ExecutionPlan

Op = Callable[[Any, Any], Any]


def _as_2d(xs) -> Tuple[jax.Array, Tuple[int, ...]]:
    leaves = jax.tree.leaves(xs)
    if len(leaves) != 1:
        raise ValueError(
            "pallas backend supports single-array inputs; got a pytree with "
            f"{len(leaves)} leaves — use backend='vector'"
        )
    x = leaves[0]
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"pallas backend requires a float dtype, got {x.dtype}"
        )
    n = x.shape[0]
    tail = x.shape[1:]
    d = int(np.prod(tail)) if tail else 1
    return x.reshape(n, d), tail


def _round_mats(plan: ExecutionPlan, dtype) -> Tuple:
    """Per-round one-hot matrices, cached on (plan, backend, dtype)."""
    from repro.kernels.tile_scan import build_round_matrices

    key = (plan_key(plan), "pallas", str(np.dtype(dtype)))
    mats = lowered_cache.get(key)
    if mats is None:
        # Concrete even under a jit trace — cached tracers would leak.
        with jax.ensure_compile_time_eval():
            mats = tuple(
                tuple(
                    None if m is None else jnp.asarray(m, dtype=dtype)
                    for m in build_round_matrices(rnd, plan.n)
                )
                for rnd in plan.rounds
            )
        lowered_cache.put(key, mats)
    return mats


def exec_pallas(
    op: Op,
    plan: ExecutionPlan,
    xs,
    *,
    interpret: Optional[bool] = None,
    **_,
) -> Tuple[Any, Any]:
    from repro.kernels.tile_scan import fused_round, tile_apply, tile_local_scan

    y2, tail = _as_2d(xs)
    n = y2.shape[0]

    if plan.n == n:
        # Rounds mode: one fused kernel per plan round.
        mats = _round_mats(plan, y2.dtype)
        total = None
        for rnd, m in zip(plan.rounds, mats):
            if rnd.capture_total is not None:
                total = y2[rnd.capture_total].reshape(tail)
            y2 = fused_round(op, y2, m, interpret=interpret)
        return y2.reshape((n,) + tail), total

    # Tiles mode: plan.n tiles, local phases fused in Pallas.
    t = plan.n
    if n % t:
        raise ValueError(f"n={n} not divisible by tile count {t}")
    local, partials = tile_local_scan(op, y2, t, interpret=interpret)
    gscan, _ = exec_vector(op, plan, partials[:, 0])
    seeds = jnp.concatenate([partials[:1, 0], gscan[:-1]], axis=0)
    out = tile_apply(op, local, seeds[:, None], interpret=interpret)
    return out.reshape((n,) + tail), None


register_backend("pallas", exec_pallas)
