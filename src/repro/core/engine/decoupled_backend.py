"""Decoupled-lookback scan backend: single-pass, device-resident.

Wraps ``kernels/lookback_scan.py`` as an engine backend named
``"decoupled"``.  Unlike the multi-pass decompositions (blocked,
hierarchical-array, pallas tiles) every element is read exactly once; the
cross-tile dependency resolves through the published tile-status board
instead of a separate global phase, so the whole scan is one fused kernel
launch that never leaves the device.

What this adapter adds around the raw kernel:

* **pytree operands** — leaves are packed column-wise into one (n, D)
  array and the operator is lifted through ``_tiling.packed_op`` (pure
  reshapes, bit-exact);
* **``where=`` masks** — an identity-flag lane rides along and the packed
  operator is lifted to the optional monoid (``_tiling.lift_masked``),
  reproducing the plan-lowering mask semantics without leaving the single
  pass;
* **seeding** — a seed element becomes tile 0's exclusive prefix, which is
  how the incremental ``SeriesSession.extend`` path folds the retained
  running total into a device-resident suffix scan;
* **arbitrary n** — rows are padded to a tile multiple by repeating the
  last row (safe: the tail tile's aggregate is never consumed, padded
  outputs are sliced off);
* **element-domain lists** — stackable element lists are stacked to the
  array domain, scanned in one launch, and unstacked.

``plan`` is ignored: the decoupled formulation has no global circuit
phase, which is the point.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels._tiling import (
    add_flag_lane,
    default_num_tiles,
    lift_masked,
    pack_element,
    pack_leaves,
    packed_op,
    pad_rows,
    unpack_leaves,
)
from repro.kernels.lookback_scan import lookback_scan

from .backends import register_backend

Op = Callable[[Any, Any], Any]


def stack_elements(xs):
    """Stack a list of same-structure pytree elements along a new leading
    axis, or return None when the elements are not stackable (mismatched
    structures/shapes, non-array leaves like RegElement's index ints)."""
    if not xs:
        return None
    try:
        ref = jax.tree.structure(xs[0])
        for x in xs[1:]:
            if jax.tree.structure(x) != ref:
                return None
        stacked = jax.tree.map(
            lambda *ts: jnp.stack([jnp.asarray(t) for t in ts], axis=0), *xs
        )
    except (TypeError, ValueError):
        return None
    leaves = jax.tree.leaves(stacked)
    if not leaves or any(not hasattr(t, "dtype") for t in leaves):
        return None
    return stacked


def exec_decoupled(
    op: Op,
    plan,
    xs,
    *,
    num_blocks: Optional[int] = None,
    seed: Any = None,
    where=None,
    interpret: Optional[bool] = None,
    **_,
) -> Tuple[Any, Any]:
    """Single-pass decoupled-lookback scan; returns ``(ys, total)``."""
    if isinstance(xs, list):
        stacked = stack_elements(xs)
        if stacked is None:
            raise ValueError(
                "decoupled backend needs stackable array elements; got a "
                "list the operator cannot be batched over — use "
                "element/worksteal/hierarchical"
            )
        ys, total = exec_decoupled(
            op, plan, stacked, num_blocks=num_blocks, seed=seed,
            where=where, interpret=interpret,
        )
        n = len(xs)
        return [jax.tree.map(lambda t, i=i: t[i], ys) for i in range(n)], total

    x2, spec = pack_leaves(xs)
    n = x2.shape[0]
    pop = packed_op(op, spec)

    masked = where is not None
    if masked:
        if len(where) != n:
            raise ValueError(f"where mask length {len(where)} != n {n}")
        x2 = add_flag_lane(x2, where)
        pop = lift_masked(pop)

    seed_row = None
    if seed is not None:
        seed_row = pack_element(seed, spec)
        if masked:
            # The seed always participates: identity flag 0.
            seed_row = jnp.concatenate(
                [seed_row, jnp.zeros((1,), x2.dtype)], axis=0
            )

    t = (num_blocks if num_blocks is not None
         else default_num_tiles(n, x2.shape[1] * x2.dtype.itemsize))
    t = max(1, min(int(t), n))
    x2p, _ = pad_rows(x2, t)

    y2p, _status, _aggs, _prefs = lookback_scan(
        pop, x2p, t, seed=seed_row, interpret=interpret
    )
    y2 = y2p[:n]
    if masked:
        y2 = y2[:, :-1]
    ys = unpack_leaves(y2, spec)
    total = jax.tree.map(lambda t: t[-1], ys)
    return ys, total


register_backend("decoupled", exec_decoupled)
