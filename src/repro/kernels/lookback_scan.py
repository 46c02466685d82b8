"""Single-pass decoupled-lookback scan kernel (LightScan, PAPERS.md).

The multi-pass decompositions (``tile_scan.py`` tiles, the blocked backend)
read every element twice: once to reduce tile aggregates, once to apply the
global prefixes.  The decoupled-lookback formulation does both in **one
pass**: each tile scans its elements locally, *publishes* its aggregate,
then resolves its exclusive prefix by walking backwards over its
predecessors' published state — stopping early at the first predecessor
that has already published an inclusive prefix:

    status[i] ∈ {EMPTY, AGG, PREFIX}
    tile i: local scan → publish (agg, AGG)
            excl ← Σ_op backwards over j = i-1, i-2, … until status[j] ==
                   PREFIX (accumulate agg[j] for AGG tiles, fold pref[j]
                   and stop at a PREFIX tile)
            publish (excl ∘ agg, PREFIX); emit excl ∘ local

Elements are touched once; cross-tile communication is O(lookback length),
which collapses to O(1) amortized because publishing a prefix terminates
every later tile's walk at this tile.

On a sequential grid (Pallas interpret mode on CPU, one TPU core) every
predecessor has already published its PREFIX when tile ``i`` runs, so the
while-loop takes exactly one step; the full protocol — including the
AGG-accumulation path — is exercised by the pure-Python twin
:func:`lookback_resolve` under adversarial interleavings in the tests.

Seeding: an optional ``seed`` row is the exclusive prefix of tile 0 (the
incremental ``SeriesSession.extend`` path folds the retained running total
in here), in which case tile 0's output is ``op(seed, local)`` instead of
``local``.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.invariants import check_board_published, check_lookback_step
from repro.analysis.sync import invariants_enabled, sync_point

from ._tiling import block_scan, resolve_interpret

Op = Callable[[jax.Array, jax.Array], jax.Array]

#: Tile-status protocol flags (published in program order).
FLAG_EMPTY = 0    # tile has published nothing yet
FLAG_AGG = 1      # tile aggregate available (no prefix yet)
FLAG_PREFIX = 2   # inclusive prefix available — lookback stops here


class LookbackProtocolError(RuntimeError):
    """A lookback read observed an unpublished (EMPTY) predecessor."""


def lookback_resolve(op, i: int, statuses, aggs, prefs):
    """Pure-Python twin of the kernel's lookback walk (for property tests).

    Resolves tile ``i``'s exclusive prefix from the published tile states.
    Returns ``(exclusive_prefix, steps)``; raises
    :class:`LookbackProtocolError` on an EMPTY predecessor (the protocol
    guarantees every predecessor has published at least its aggregate
    before tile ``i`` starts its walk).
    """
    if i <= 0:
        raise ValueError("tile 0 has no predecessors to resolve against")
    checking = invariants_enabled()
    acc = None
    steps = 0
    for j in range(i - 1, -1, -1):
        st = statuses[j]
        if checking:
            # Debug runs route every read through the shared invariant
            # module (same checks the schedule explorer asserts) before
            # the protocol error below.
            sync_point("lookback.read")
            check_lookback_step(i, j, int(st), stopped=(st == FLAG_PREFIX))
        if st == FLAG_EMPTY:
            raise LookbackProtocolError(
                f"tile {i} read EMPTY status at predecessor {j}"
            )
        v = prefs[j] if st == FLAG_PREFIX else aggs[j]
        acc = v if acc is None else op(v, acc)
        steps += 1
        if st == FLAG_PREFIX:
            return acc, steps
    raise LookbackProtocolError(
        f"tile {i} walked past tile 0 without finding a PREFIX"
    )


def lookback_scan(
    op: Op,
    x: jax.Array,
    num_tiles: int,
    *,
    seed: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass decoupled-lookback inclusive scan of ``x`` (n, d).

    ``op`` must be batched over the leading axis (it is applied to (m, d)
    row blocks).  ``n`` must divide ``num_tiles`` (see
    ``_tiling.pad_rows``); each grid step holds one whole tile, so size
    tiles with ``_tiling.vmem_tiles``.  ``seed``: optional (d,) or (1, d)
    exclusive prefix of the whole scan.

    Returns ``(y, status, aggs, prefs)``: the (n, d) inclusive scan plus
    the published per-tile protocol state ((t, 1) int32 statuses, (t, d)
    aggregates, (t, d) inclusive prefixes) for inspection/testing.
    """
    n, d = x.shape
    t = int(num_tiles)
    if t < 1:
        raise ValueError(f"num_tiles must be >= 1, got {t}")
    k = n // t
    if k * t != n:
        raise ValueError(f"n={n} not divisible by num_tiles={t}")
    x3 = x.reshape(t, k, d)
    has_seed = seed is not None
    seed_row = (
        jnp.asarray(seed, x.dtype).reshape(1, d)
        if has_seed else jnp.zeros((1, d), x.dtype)
    )

    def kernel(x_ref, seed_ref, y_ref, status_ref, agg_ref, pref_ref):
        i = pl.program_id(0)

        # The status board lives in one full-view output block shared by
        # all grid steps (constant index_map); zero it before tile 0 runs.
        @pl.when(i == 0)
        def _init():
            status_ref[...] = jnp.zeros_like(status_ref)

        local = block_scan(op, x_ref[0])                      # (K, d)
        agg = local[k - 1:]                                   # (1, d)
        agg_ref[pl.ds(i, 1), :] = agg
        status_ref[pl.ds(i, 1), :] = jnp.full((1, 1), FLAG_AGG, jnp.int32)

        def resolve(_):
            # Walk back over predecessors: accumulate AGG aggregates,
            # fold in the first PREFIX and stop (lookback_resolve twin).
            def read(j):
                st = status_ref[pl.ds(j, 1), :][0, 0]
                a = agg_ref[pl.ds(j, 1), :]
                p = pref_ref[pl.ds(j, 1), :]
                return st, jnp.where(st == FLAG_PREFIX, p, a)

            st0, v0 = read(i - 1)

            def cond(c):
                _j, _acc, found = c
                return jnp.logical_not(found)

            def body(c):
                j, acc, _ = c
                st, v = read(j)
                return j - 1, op(v, acc), st == FLAG_PREFIX

            _, acc, _ = jax.lax.while_loop(
                cond, body, (i - 2, v0, st0 == FLAG_PREFIX)
            )
            return acc

        excl0 = seed_ref[...] if has_seed else jnp.zeros((1, d), x.dtype)
        excl = jax.lax.cond(i == 0, lambda _: excl0, resolve, 0)
        if has_seed:
            out = op(jnp.broadcast_to(excl, local.shape), local)
            incl = op(excl, agg)
        else:
            out = jnp.where(
                i == 0, local,
                op(jnp.broadcast_to(excl, local.shape), local),
            )
            incl = jnp.where(i == 0, agg, op(excl, agg))
        y_ref[0] = out
        pref_ref[pl.ds(i, 1), :] = incl
        status_ref[pl.ds(i, 1), :] = jnp.full((1, 1), FLAG_PREFIX, jnp.int32)

    def blk(*shape):
        return pl.BlockSpec((1,) + shape, lambda i: (i,) + (0,) * len(shape))

    def full(*shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    y, status, aggs, prefs = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[blk(k, d), full(1, d)],
        out_specs=(blk(k, d), full(t, 1), full(t, d), full(t, d)),
        out_shape=(
            jax.ShapeDtypeStruct((t, k, d), x.dtype),
            jax.ShapeDtypeStruct((t, 1), jnp.int32),
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, d), x.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(x3, seed_row)
    if invariants_enabled():
        # Terminal board state (debug runs only — forces a device sync):
        # every tile must have published its inclusive PREFIX.
        sync_point("lookback.publish_prefix")
        check_board_published([int(s) for s in jax.device_get(status)[:, 0]])
    return y.reshape(n, d), status, aggs, prefs
