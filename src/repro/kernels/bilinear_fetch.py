"""The four bilinear neighbours of every pixel of a warp, without a gather.

A TPU gathers one scalar per index at the cost of a whole memory tile, so
``img[r0, c0]`` over a 1920 x 1856 frame (four of them per warp, two warps
per gradient step) is the whole cost of a registration there.  This kernel
holds the edge-padded source image in VMEM and walks the output in
(``TILE_ROWS`` x 128) tiles.  A rigid warp maps a tile's pixel at row ``y``
to a source row ``y + d`` with ``d`` in a narrow band (its width grows with
the rotation angle); for each ``d`` of the band the kernel loads the
8-row source slab at that offset and picks every pixel's column out of it
with lane gathers (``jnp.take_along_axis`` within a 128-lane vector), keeping
the picks of the pixels whose row offset is ``d``.  Any deformation is
exact; a larger angle only widens the band.

Only the integer neighbour indices enter the kernel, so nothing in it is
differentiated: the bilinear blend (and its derivative) stays in JAX.
``interpret=None`` compiles for a TPU and interprets elsewhere
(``_tiling.resolve_interpret``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tiling import resolve_interpret

LANES = 128
SUB = 8
#: Output rows per grid step (``TILE_ROWS // SUB`` slab loops per step).
TILE_ROWS = 32
#: Source columns one tile can reach: its 128 output columns, their drift
#: under any rotation over ``TILE_ROWS`` rows, and the 128-lane alignment.
SPAN = 3 * LANES
#: Largest padded source image the kernel keeps in VMEM (bytes); larger
#: images take the gather path of ``deformation.warp``.
VMEM_IMAGE_BYTES = 40 << 20


def padded_shape(shape: Tuple[int, int]) -> Tuple[int, int]:
    """Edge-padded (rows, cols) of an (H, W) source image in the kernel."""
    h, w = shape
    return -(-(h + 4 * SUB) // SUB) * SUB, -(-w // LANES) * LANES + SPAN


def fits(shape: Tuple[int, int]) -> bool:
    rows, cols = padded_shape(shape)
    return rows * cols * 4 <= VMEM_IMAGE_BYTES


def _pick(slab, idx):
    """slab[s, idx[s, l]] for an (8, SPAN) slab and (8, 128) indices."""
    lane = jnp.bitwise_and(idx, LANES - 1)
    chunk = jnp.right_shift(idx, 7)
    out = jnp.take_along_axis(slab[:, :LANES], lane, axis=1)
    for j in range(1, SPAN // LANES):
        part = jnp.take_along_axis(slab[:, j * LANES:(j + 1) * LANES], lane,
                                   axis=1)
        out = jnp.where(chunk == j, part, out)
    return out


def _fetch_kernel(img_ref, r0_ref, c0_ref, out_ref):
    ti = pl.program_id(0)
    zero = jnp.zeros((SUB, LANES), jnp.float32)
    for g in range(TILE_ROWS // SUB):
        rows = slice(g * SUB, (g + 1) * SUB)
        y0 = ti * TILE_ROWS + g * SUB          # output row of sublane 0
        r0 = r0_ref[rows, :]
        c0 = c0_ref[rows, :]
        dr = r0 - (y0 + jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0))
        cb = jnp.min(c0) // LANES * LANES
        lc = c0 - cb
        lc1 = lc + 1

        def band(d, acc, dr=dr, lc=lc, lc1=lc1, y0=y0, cb=cb):
            v00, v01, v10, v11 = acc
            # Output sublane s at offset d reads padded row SUB + y0 + s + d:
            # an aligned 16-row load, rotated up by d mod 8 (Mosaic loads
            # only at multiples of 8 rows).
            top = pl.multiple_of(SUB + y0 + d // SUB * SUB, SUB)
            two = img_ref[pl.ds(top, 2 * SUB),
                          pl.ds(pl.multiple_of(cb, LANES), SPAN)]
            slab = pltpu.roll(two, (2 * SUB - d % SUB) % (2 * SUB), 0)[:SUB]
            a = _pick(slab, lc)
            b = _pick(slab, lc1)
            here = dr == d
            below = dr + 1 == d
            return (jnp.where(here, a, v00), jnp.where(here, b, v01),
                    jnp.where(below, a, v10), jnp.where(below, b, v11))

        v = jax.lax.fori_loop(jnp.min(dr), jnp.max(dr) + 2, band,
                              (zero, zero, zero, zero))
        for k in range(4):
            out_ref[k, rows, :] = v[k]


def bilinear_fetch(
    img: jax.Array,
    r0: jax.Array,
    c0: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``(4, H', W')``: ``img`` at ``(r0, c0)``, ``(r0, c0+1)``, ``(r0+1, c0)``
    and ``(r0+1, c0+1)``, a neighbour past the last row or column reading
    the edge (as ``min(r0 + 1, H - 1)`` would).

    ``r0``/``c0`` are int32 ``(H', W')`` indices in ``[0, H-1] x [0, W-1]``.
    """
    h, w = img.shape
    ho, wo = r0.shape
    rows, cols = padded_shape((h, w))
    hp = -(-ho // TILE_ROWS) * TILE_ROWS
    wp = -(-wo // LANES) * LANES
    # Padding rows and columns repeat the last real indices (in bounds;
    # their results are dropped).
    r0 = jnp.pad(r0, ((0, hp - ho), (0, wp - wo)), mode="edge")
    c0 = jnp.pad(c0, ((0, hp - ho), (0, wp - wo)), mode="edge")
    src = jnp.pad(img, ((SUB, rows - SUB - h), (0, cols - w)), mode="edge")
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i, j: (i, j))
    out = pl.pallas_call(
        _fetch_kernel,
        grid=(hp // TILE_ROWS, wp // LANES),
        in_specs=[pl.BlockSpec((rows, cols), lambda i, j: (0, 0)), tile, tile],
        out_specs=pl.BlockSpec((4, TILE_ROWS, LANES), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((4, hp, wp), jnp.float32),
        compiler_params=_compiler_params(rows * cols * 4),
        interpret=resolve_interpret(interpret),
    )(src.astype(jnp.float32), r0, c0)
    return out[:, :ho, :wo]


def _compiler_params(image_bytes: int):
    # The image block is double-buffered; the tiles and slabs are small.
    return pltpu.CompilerParams(vmem_limit_bytes=2 * image_bytes + (16 << 20))
