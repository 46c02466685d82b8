"""Rigid deformations and differentiable image warping (paper §2.3.1).

A rigid deformation is phi(x) = R(alpha) (x - c) + c + G — rotation by alpha
about the image centre c plus translation G (in pixels).  Stored as a pytree
``{"angle": (), "shift": (2,)}`` so it vmaps/scans/shards like any other JAX
value; the 3 floats match the paper's 20-byte payload (3 floats + 2 indices).

Composition convention (§2.3.2): elements of the series-registration scan are
phi_{i,j} with  f_j o phi_{i,j} ~= f_i.  The scan operator's initial guess is

    compose(phi_{i,j}, phi_{j,k}) = phi_{j,k} o phi_{i,j}

since f_k o (phi_{j,k} o phi_{i,j}) = (f_k o phi_{j,k}) o phi_{i,j}
~= f_j o phi_{i,j} ~= f_i.  Rigid transforms are closed and *associative*
under composition and non-commutative — the canonical scan element.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

Deformation = Dict[str, jax.Array]

#: Products of rotations with pixel coordinates in full float32.  A TPU
#: multiplies float32 matrices in one bfloat16 pass by default, whose 8-bit
#: mantissa puts a coordinate near 1000 px off by up to 2 px.
_EXACT = jax.lax.Precision.HIGHEST


def identity_deformation(dtype=jnp.float32) -> Deformation:
    return {"angle": jnp.zeros((), dtype), "shift": jnp.zeros((2,), dtype)}


def make_deformation(angle, shift) -> Deformation:
    return {
        "angle": jnp.asarray(angle, jnp.float32),
        "shift": jnp.asarray(shift, jnp.float32),
    }


def rotation_matrix(angle: jax.Array) -> jax.Array:
    c, s = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([jnp.stack([c, -s]), jnp.stack([s, c])])


def compose(a: Deformation, b: Deformation) -> Deformation:
    """b o a  (apply ``a`` first): the series-scan initial-guess operator.

    With phi(x) = R(alpha)(x-c) + c + G (all about the same centre c):
      b(a(x)) = R(ab)(x-c) + c + R(b) G_a + G_b ,  alpha_ab = alpha_a + alpha_b.
    Batched over any leading axes (used by the vectorized circuit executor).
    """
    angle = a["angle"] + b["angle"]
    rb = rotation_matrix(b["angle"])  # (..., 2, 2) when batched
    if a["shift"].ndim == 1:
        shift = jnp.matmul(rb, a["shift"], precision=_EXACT) + b["shift"]
    else:
        shift = jnp.einsum("ij...,...j->...i", rb, a["shift"],
                           precision=_EXACT) + b["shift"]
    return {"angle": angle, "shift": shift}


def compose_batched(a: Deformation, b: Deformation) -> Deformation:
    """Leading-axis-batched compose (the circuit-executor operator contract)."""
    angle = a["angle"] + b["angle"]
    c, s = jnp.cos(b["angle"]), jnp.sin(b["angle"])
    ax, ay = a["shift"][..., 0], a["shift"][..., 1]
    shift = jnp.stack([c * ax - s * ay, s * ax + c * ay], axis=-1) + b["shift"]
    return {"angle": angle, "shift": shift}


# Pure composition accepts operands stacked along a new leading axis — the
# dispatcher may run element-domain phase 1 as one vmapped device launch
# instead of WorkerPool threads (engine/cost.py: Dispatch.device_phase1).
# Batchable ops form a monoid: the declared identity is what padding /
# `where=` mask lifting folds in without changing any prefix (the
# operator-contract lint pass OPC002 enforces the declaration).
compose_batched.op_batchable = True
compose_batched.op_identity = identity_deformation


def inverse(d: Deformation) -> Deformation:
    """phi^{-1}: R(-a)(x - c - G) + c."""
    ang = -d["angle"]
    r = rotation_matrix(ang)
    return {"angle": ang, "shift": -jnp.matmul(r, d["shift"], precision=_EXACT)}


def _bilinear_sample(img: jax.Array, coords: jax.Array, kernel: bool = False):
    """Sample img[H, W] at float coords[..., 2] (row, col), edge-clamped.

    ``kernel``: fetch the four neighbours with the Pallas kernel
    (``kernels/bilinear_fetch.py``) instead of four gathers.
    """
    h, w = img.shape
    r = jnp.clip(coords[..., 0], 0.0, h - 1.0)
    c = jnp.clip(coords[..., 1], 0.0, w - 1.0)
    r0 = jnp.floor(r).astype(jnp.int32)
    c0 = jnp.floor(c).astype(jnp.int32)
    fr = r - r0
    fc = c - c0
    if kernel:
        from repro.kernels.bilinear_fetch import bilinear_fetch

        v00, v01, v10, v11 = bilinear_fetch(img, r0, c0)
    else:
        r1 = jnp.minimum(r0 + 1, h - 1)
        c1 = jnp.minimum(c0 + 1, w - 1)
        v00 = img[r0, c0]
        v01 = img[r0, c1]
        v10 = img[r1, c0]
        v11 = img[r1, c1]
    top = v00 * (1 - fc) + v01 * fc
    bot = v10 * (1 - fc) + v11 * fc
    return top * (1 - fr) + bot * fr


def warp(img: jax.Array, d: Deformation, *, kernel: bool | None = None):
    """(T o phi)(x) = T(phi(x)): deform template ``img`` by ``d``.

    Differentiable w.r.t. ``d`` (bilinear interpolation).  ``kernel=None``
    fetches the neighbours with the Pallas kernel on a TPU (per-pixel
    gathers cost a memory tile each there) when the image fits its VMEM,
    with gathers elsewhere; ``True`` forces the kernel (interpreted off the
    TPU), ``False`` the gathers.  Both give the same values.
    """
    h, w = img.shape
    if kernel is None:
        from repro.kernels.bilinear_fetch import fits

        kernel = jax.default_backend() == "tpu" and fits((h, w))
    ctr = jnp.array([(h - 1) / 2.0, (w - 1) / 2.0])
    rows = jnp.arange(h, dtype=jnp.float32)
    cols = jnp.arange(w, dtype=jnp.float32)
    grid = jnp.stack(jnp.meshgrid(rows, cols, indexing="ij"), axis=-1)  # (H,W,2)
    rel = grid - ctr
    rot = rotation_matrix(d["angle"])
    coords = jnp.einsum("ij,hwj->hwi", rot, rel, precision=_EXACT)
    coords = coords + ctr + d["shift"]
    return _bilinear_sample(img, coords, kernel)


def ncc(a: jax.Array, b: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Normalized cross-correlation in [-1, 1] (paper's distance, §2.3.1)."""
    a = a - a.mean()
    b = b - b.mean()
    denom = jnp.sqrt((a * a).sum() * (b * b).sum()) + eps
    return (a * b).sum() / denom


def ncc_distance(ref: jax.Array, tmpl: jax.Array, d: Deformation) -> jax.Array:
    """D(R, T o phi) = 1 - NCC(R, T o phi)  (0 at perfect alignment)."""
    return 1.0 - ncc(ref, warp(tmpl, d))


def downsample2(img: jax.Array) -> jax.Array:
    """2x average-pool (the multilevel pyramid step)."""
    h, w = img.shape
    h2, w2 = h // 2 * 2, w // 2 * 2
    x = img[:h2, :w2].reshape(h2 // 2, 2, w2 // 2, 2)
    return x.mean(axis=(1, 3))
