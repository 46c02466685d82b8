"""Pallas kernels for the engine's tile-scan backend.

Two execution shapes for *low-compute* operators (add/max/logsumexp-class),
both driven by a precompiled :class:`repro.core.engine.plan.ExecutionPlan`:

1. **Fused round kernels** (``fused_round``): one kernel per plan round.  The
   round's static gather/scatter index sets are lowered to one-hot matrices at
   plan time, so a round executes as three MXU matmuls around one vectorized
   operator application:

       out = y * keep + SC @ op(GA @ y, GB @ y) + SM @ (GM @ y)

   One-hot gathers/scatters are exact in floating point (each output row sums
   a single non-zero term) and avoid dynamic-index loads, which Mosaic
   restricts; ``keep`` zeroes exactly the rows the round rewrites.

2. **Tile kernels** (``tile_local_scan`` / ``tile_apply``): the paper's
   local–global–local decomposition (§4.1) with the two local phases fused
   into one kernel launch each.  ``tile_local_scan`` computes per-tile
   inclusive scans (``_tiling.block_scan`` on the VPU) plus tile totals;
   the tiny global phase over tile totals runs outside (the engine's vector
   executor on the plan); ``tile_apply`` folds each tile's exclusive global
   prefix back in with a single batched operator application.

``interpret=None`` compiles the kernels via Mosaic on a TPU and interprets
them anywhere else (``_tiling.resolve_interpret``).  Feature dims should be
padded to the 128-lane width for peak MXU utilization; correctness does not
depend on it.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Shared with kernels/lookback_scan.py — the one-hot lowering and tile
# padding helpers live in _tiling.py; re-exported here for compatibility.
from ._tiling import build_round_matrices  # noqa: F401
from ._tiling import block_scan, resolve_interpret

Op = Callable[[Any, Any], Any]


def _full_spec(*shape):
    return pl.BlockSpec(shape, lambda: (0,) * len(shape))


def _tile_spec(*shape):
    """Block (1, *shape) of a (T, *shape) array: one tile per grid step."""
    return pl.BlockSpec((1,) + shape, lambda i: (i,) + (0,) * len(shape))


def fused_round(
    op: Op, y: jax.Array, mats, *, interpret: Optional[bool] = None
) -> jax.Array:
    """Execute one plan round as a fused gather–combine–scatter kernel.

    ``y``: (n, d) wire values; ``mats``: output of :func:`build_round_matrices`
    cast to ``y.dtype``.
    """
    ga, gb, sc, gm, sm, keep = mats
    has_c = ga is not None
    has_m = gm is not None
    if not has_c and not has_m:
        return y
    n, d = y.shape
    # Accumulate at (at least) f32; never *below* the wire dtype — an f64
    # scan must not round through f32 on every round.
    acc_dt = jnp.promote_types(y.dtype, jnp.float32)

    args = [y]
    specs = [_full_spec(n, d)]
    for a in (ga, gb, sc) if has_c else ():
        args.append(a)
        specs.append(_full_spec(*a.shape))
    for a in (gm, sm) if has_m else ():
        args.append(a)
        specs.append(_full_spec(*a.shape))
    args.append(keep)
    specs.append(_full_spec(n, 1))

    def kernel(*refs):
        y_ref, rest, o_ref = refs[0], refs[1:-1], refs[-1]
        i = 0
        yv = y_ref[...]
        keep_v = rest[-1][...]
        acc = yv * keep_v
        if has_c:
            ga_v, gb_v, sc_v = (rest[i][...], rest[i + 1][...], rest[i + 2][...])
            i += 3
            a = jax.lax.dot_general(
                ga_v, yv, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(yv.dtype)
            b = jax.lax.dot_general(
                gb_v, yv, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(yv.dtype)
            r = op(a, b)
            acc = acc + jax.lax.dot_general(
                sc_v, r, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(yv.dtype)
        if has_m:
            gm_v, sm_v = rest[i][...], rest[i + 1][...]
            mv = jax.lax.dot_general(
                gm_v, yv, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(yv.dtype)
            acc = acc + jax.lax.dot_general(
                sm_v, mv, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(yv.dtype)
        o_ref[...] = acc

    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=specs,
        out_specs=_full_spec(n, d),
        out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
        interpret=resolve_interpret(interpret),
    )(*args)


# ---------------------------------------------------------------------------
# Tile kernels: fused local phases of the local-global-local decomposition
# ---------------------------------------------------------------------------


def tile_local_scan(
    op: Op, x: jax.Array, num_tiles: int, *, interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array]:
    """Per-tile inclusive scans and tile totals in one kernel launch.

    ``x``: (n, d) with n divisible by ``num_tiles``; each grid step holds
    one whole tile, so size tiles with ``_tiling.vmem_tiles``.
    Returns (local, partials): (T, K, d) per-tile inclusive scans and
    (T, 1, d) tile totals for the global phase (a rank-1 ``(d,)`` block
    would break the TPU's (8, 128) block rule; ``(1, d)`` equals the
    array's trailing dims and passes).
    """
    n, d = x.shape
    t = num_tiles
    k = n // t
    if k * t != n:
        raise ValueError(f"n={n} not divisible by num_tiles={t}")
    x3 = x.reshape(t, k, d)

    def kernel(x_ref, y_ref, p_ref):
        loc = block_scan(op, x_ref[0])                   # (K, d)
        y_ref[0] = loc
        p_ref[0] = loc[k - 1:]

    local, partials = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[_tile_spec(k, d)],
        out_specs=(_tile_spec(k, d), _tile_spec(1, d)),
        out_shape=(
            jax.ShapeDtypeStruct((t, k, d), x.dtype),
            jax.ShapeDtypeStruct((t, 1, d), x.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(x3)
    return local, partials


def tile_apply(
    op: Op, local: jax.Array, seeds: jax.Array, *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fold each tile's exclusive global prefix into its local scan.

    ``local``: (T, K, d); ``seeds``: (T, 1, d) where seeds[i] is the
    inclusive global scan of tiles < i (seeds[0] is ignored — tile 0
    passes through).  Returns the flat (T*K, d) inclusive scan.
    """
    t, k, d = local.shape
    # Mosaic refuses to broadcast a (1, d) seed block across the rows
    # ("Invalid input layout") when the operator then slices lanes; a
    # whole (8, d) sublane tile, sliced after the load, lowers.
    seeds8 = jnp.broadcast_to(seeds, (t, 8, d))

    def kernel(y_ref, s_ref, o_ref):
        i = pl.program_id(0)
        y = y_ref[0]                                     # (K, d)
        comb = op(jnp.broadcast_to(s_ref[0][:1], y.shape), y)
        o_ref[0] = jnp.where(i == 0, y, comb)

    out = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[_tile_spec(k, d), _tile_spec(8, d)],
        out_specs=_tile_spec(k, d),
        out_shape=jax.ShapeDtypeStruct((t, k, d), local.dtype),
        interpret=resolve_interpret(interpret),
    )(local, seeds8)
    return out.reshape(t * k, d)
