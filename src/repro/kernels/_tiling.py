"""Shared tiling helpers for the scan kernels.

Both Pallas scan kernels (``tile_scan.py``'s local–global–local tiles and
``lookback_scan.py``'s single-pass decoupled lookback) need the same
plumbing around the kernel proper:

* **one-hot round matrices** (:func:`build_round_matrices`) lowering a
  ``PlanRound``'s static gather/scatter index sets to MXU matmuls — used by
  the fused round kernels and the Pallas backend lowering cache;
* **tile sizing and padding** (:func:`default_num_tiles`,
  :func:`pad_rows`) — kernels want ``n`` divisible by the tile count; the
  pad rows repeat the last element so a padded tail tile stays a valid
  scan segment (its aggregate is never consumed: only *earlier* tiles are
  read during lookback, and padded outputs are sliced off);
* **pytree packing** (:func:`pack_leaves` / :func:`unpack_leaves` /
  :func:`packed_op`) — the kernels operate on a single ``(n, D)`` array,
  so multi-leaf operands (e.g. ``Deformation = {"angle": (), "shift":
  (2,)}``) are flattened column-wise and the operator is wrapped to
  unpack → apply → repack (pure reshapes/concats, exact in floating
  point and fused by XLA);
* **identity-flag lifting** (:func:`lift_masked`) — ``where=`` masks ride
  along as one extra lane holding 1.0 for "this element is the operator
  identity"; the lifted operator is associative whenever the base operator
  is, and reproduces the engine's mask semantics (masked positions output
  the prefix of the valid elements before them; positions before the first
  valid element pass through unchanged).

Extracted from ``tile_scan.py`` so the two kernels cannot drift apart.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np

Op = Callable[[Any, Any], Any]


def build_round_matrices(rnd, n: int):
    """One-hot gather/scatter matrices + keep mask for one PlanRound.

    Returns (ga, gb, sc, gm, sm, keep): combine gathers (m, n), combine
    scatter (n, m), move gather (q, n), move scatter (n, q), keep (n, 1).
    Combine/move groups are None when empty.
    """
    m = rnd.num_combines
    q = rnd.num_moves
    keep = np.ones((n, 1), dtype=np.float32)
    ga = gb = sc = gm = sm = None
    if m:
        ga = np.zeros((m, n), dtype=np.float32)
        gb = np.zeros((m, n), dtype=np.float32)
        sc = np.zeros((n, m), dtype=np.float32)
        for i, (a, b, out, _fan, _cs) in enumerate(rnd.combines):
            ga[i, a] = 1.0
            gb[i, b] = 1.0
            sc[out, i] = 1.0
            keep[out, 0] = 0.0
    if q:
        gm = np.zeros((q, n), dtype=np.float32)
        sm = np.zeros((n, q), dtype=np.float32)
        for i, (src, out, _fan) in enumerate(rnd.moves):
            gm[i, src] = 1.0
            sm[out, i] = 1.0
            keep[out, 0] = 0.0
    return ga, gb, sc, gm, sm, keep


# ---------------------------------------------------------------------------
# platform + in-kernel scan
# ---------------------------------------------------------------------------


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret=None`` means: compile for a TPU, interpret anywhere else.

    Mosaic compiles Pallas kernels for the TPU only; elsewhere (CPU tests)
    the same bodies run in interpret mode.  The one place that rule lives.
    """
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() != "tpu"


def block_scan(op: Op, x):
    """Inclusive scan of a (K, d) block along rows, inside a Pallas kernel.

    Hillis–Steele: log2(K) full-block steps, each combining every row with
    the row ``s`` above it (a sublane rotation, masked where ``i < s``).
    ``lax.associative_scan`` does not lower on the TPU (its strided
    slices are refused), this does; ``op`` must act row-wise on (K, d).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    k = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < k:
        x = jnp.where(row >= s, op(pltpu.roll(x, s, 0), x), x)
        s *= 2
    return x


# ---------------------------------------------------------------------------
# tile sizing + padding
# ---------------------------------------------------------------------------

#: Largest (rows, d) block one grid step of a scan kernel holds.  The TPU
#: compiler refuses a block scan of 4 MiB blocks against the 16 MiB scoped
#: VMEM of a v5e (in and out blocks are double-buffered, plus the scan's
#: temporaries); 1 MiB leaves room for the lookback boards.
TILE_BLOCK_BYTES = 1 << 20


def vmem_tiles(n: int, row_bytes: int, at_least: int = 1) -> int:
    """Fewest tiles (>= ``at_least``) whose blocks fit ``TILE_BLOCK_BYTES``."""
    need = -(-n * max(int(row_bytes), 1) // TILE_BLOCK_BYTES)
    return max(1, int(at_least), min(need, n))


def default_num_tiles(n: int, row_bytes: int = 4) -> int:
    """Tile count for an n-element single-pass scan.

    Small inputs run as one tile (the lookback machinery is pure overhead
    below ~2 tiles); otherwise 16 tiles, or as many more as it takes for
    each tile's block to fit ``TILE_BLOCK_BYTES`` of VMEM.
    """
    if n < 32:
        return 1
    return vmem_tiles(n, row_bytes, max(1, min(16, n // 16)))


def pad_rows(x2, num_tiles: int):
    """Pad ``x2`` (n, d) so its row count divides ``num_tiles``.

    Pad rows repeat the last row: the padded tail is still a monotone scan
    segment, and its outputs/aggregate are sliced off / never read.
    Returns ``(padded, n)`` with the original row count.
    """
    import jax.numpy as jnp

    n = x2.shape[0]
    k = -(-n // num_tiles)  # ceil
    m = k * num_tiles
    if m == n:
        return x2, n
    pad = jnp.broadcast_to(x2[n - 1 : n], (m - n,) + x2.shape[1:])
    return jnp.concatenate([x2, pad], axis=0), n


# ---------------------------------------------------------------------------
# pytree <-> (n, D) packing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Layout of a pytree packed column-wise into one (n, D) array."""

    treedef: Any
    tails: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    widths: Tuple[int, ...]
    dtype: Any                       # common packed dtype

    @property
    def dim(self) -> int:
        return sum(self.widths)


def pack_leaves(xs) -> Tuple[Any, PackSpec]:
    """Flatten a pytree of (n, *tail) arrays into one (n, D) array."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(xs)
    if not leaves:
        raise ValueError("cannot pack an empty pytree")
    n = leaves[0].shape[0]
    tails = tuple(tuple(t.shape[1:]) for t in leaves)
    dtypes = tuple(t.dtype for t in leaves)
    widths = tuple(int(np.prod(tl)) if tl else 1 for tl in tails)
    common = dtypes[0]
    for dt in dtypes[1:]:
        common = jnp.promote_types(common, dt)
    spec = PackSpec(treedef, tails, dtypes, widths, common)
    cols = [
        jnp.asarray(t).reshape(n, w).astype(common)
        for t, w in zip(leaves, widths)
    ]
    return (cols[0] if len(cols) == 1 and widths[0] == spec.dim
            else jnp.concatenate(cols, axis=1)), spec


def unpack_leaves(y2, spec: PackSpec):
    """Inverse of :func:`pack_leaves` for a (n, D) array."""
    import jax

    n = y2.shape[0]
    leaves = []
    off = 0
    for tail, dt, w in zip(spec.tails, spec.dtypes, spec.widths):
        col = y2[:, off : off + w]
        leaves.append(col.reshape((n,) + tail).astype(dt))
        off += w
    return jax.tree.unflatten(spec.treedef, leaves)


def pack_element(x, spec: PackSpec):
    """Pack a single element (pytree of ``tail``-shaped leaves) to (D,)."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(x)
    cols = [
        jnp.asarray(t).reshape(w).astype(spec.dtype)
        for t, w in zip(leaves, spec.widths)
    ]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=0)


def packed_op(op: Op, spec: PackSpec) -> Op:
    """Lift ``op`` (pytree-batched) to act on packed (m, D) rows.

    Unpack → apply → repack is reshapes and concats only, so the packed
    operator is bit-identical to the original and stays associative.
    """
    import jax.numpy as jnp

    def pop(a2, b2):
        y = op(unpack_leaves(a2, spec), unpack_leaves(b2, spec))
        import jax

        leaves = jax.tree.leaves(y)
        m = a2.shape[0]
        cols = [
            t.reshape(m, w).astype(spec.dtype)
            for t, w in zip(leaves, spec.widths)
        ]
        return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)

    return pop


# ---------------------------------------------------------------------------
# where-mask support: identity-flag lane
# ---------------------------------------------------------------------------


def add_flag_lane(x2, where: Optional[Sequence[bool]]):
    """Append one lane: 1.0 = "this row is the operator identity".

    ``where`` follows the engine convention (True = valid); None marks
    every row valid (used for seed rows, which always participate).
    """
    import jax.numpy as jnp

    n = x2.shape[0]
    if where is None:
        flags = jnp.zeros((n, 1), x2.dtype)
    else:
        flags = jnp.asarray(
            [0.0 if bool(v) else 1.0 for v in where], x2.dtype
        ).reshape(n, 1)
    return jnp.concatenate([x2, flags], axis=1)


def lift_masked(pop: Op) -> Op:
    """Lift a packed operator to the "optional monoid" over flagged rows.

    An identity-flagged operand passes the other operand through; the
    result is flagged identity only when both operands are.  Associative
    whenever ``pop`` is, and matches the plan-lowering ``where`` semantics
    (identity combines compile to moves there; here they select).
    """
    import jax.numpy as jnp

    def lifted(a, b):
        va, fa = a[:, :-1], a[:, -1:]
        vb, fb = b[:, :-1], b[:, -1:]
        v = pop(va, vb)
        v = jnp.where(fa == 1.0, vb, jnp.where(fb == 1.0, va, v))
        return jnp.concatenate([v, fa * fb], axis=1)

    return lifted
