"""Synthetic near-periodic electron-microscopy-like image series.

The paper's TEM data (1920x1856 @ 400 fps aluminum-oxidation series) is not
public; we generate frames with the same structural properties that make the
registration problem hard and the scan operator imbalanced:

  * (nearly) periodic atomic lattice  -> registration ambiguous mod period;
  * per-frame rigid drift (random walk, steps < period/2 so the neighbouring-
    frame assumption of §2.3.2 holds);
  * heavy shot noise (low-dose imaging)  -> unpredictable minimiser cost.
"""

from __future__ import annotations

from typing import Iterator, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.deformation import Deformation, make_deformation, warp

#: Frame size: ``n`` for n x n frames, or ``(H, W)`` (the paper's frames
#: are 1920 x 1856).
Size = Union[int, Tuple[int, int]]


def _hw(size: Size) -> Tuple[int, int]:
    if isinstance(size, int):
        return size, size
    h, w = size
    return int(h), int(w)


def lattice_image(
    size: Size = 96,
    period: float = 12.0,
    key: jax.Array | None = None,
    distortion: float = 0.15,
) -> jax.Array:
    """Near-periodic lattice: sum of two cosine gratings + random low-frequency
    distortion field (the 'deviations' that carry the material signal)."""
    if key is None:
        key = jax.random.PRNGKey(1410)
    h, w = _hw(size)
    y, x = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    img = (
        jnp.cos(2 * jnp.pi * x / period)
        + jnp.cos(2 * jnp.pi * y / period)
        + 0.5 * jnp.cos(2 * jnp.pi * (x + y) / (period * jnp.sqrt(2.0)))
    )
    k1, k2 = jax.random.split(key)
    # Low-frequency defects: a few Gaussian blobs that break perfect symmetry.
    nblobs = 6
    cx = jax.random.uniform(k1, (nblobs,)) * w
    cy = jax.random.uniform(k2, (nblobs,)) * h
    for i in range(nblobs):
        img = img + distortion * jnp.exp(
            -(((x - cx[i]) ** 2 + (y - cy[i]) ** 2) / (2 * (period * 0.8) ** 2))
        ) * (1.0 if i % 2 == 0 else -1.0)
    img = (img - img.mean()) / (img.std() + 1e-6)
    return img


@jax.jit
def _render_frame(base, shift, rot, nkey, noise):
    # f_i(x) = f_0(phi^{-1}(x)) so that f_i(phi(x)) = f_0(x):
    # warp() samples f_0 at phi_inv(x) when given the inverse deformation.
    inv = make_deformation(-rot, -shift)  # small-angle inverse approx.
    frame = warp(base, inv)
    return frame + noise * jax.random.normal(nkey, frame.shape)


def make_series(
    key: jax.Array,
    n_frames: int,
    size: Size = 96,
    period: float = 12.0,
    drift_step: float | None = None,
    rotation_step: float = 0.002,
    noise: float = 0.25,
) -> Tuple[jax.Array, Deformation]:
    """Returns (frames[N,H,W], true cumulative deformations phi_{0,i}).

    frames[i] is the base lattice observed after cumulative drift d_i, i.e.
    f_i o phi_{0,i} ~= f_0 with phi_{0,i} = translation(d_i) (+ tiny rotation).
    Per-step drift magnitude stays < period/2 (paper's §2.3.2 assumption).
    One batched render — the single-chunk case of :func:`stream_series`.
    """
    chunks, true = stream_series(
        key, n_frames, chunk_size=n_frames, size=size, period=period,
        drift_step=drift_step, rotation_step=rotation_step, noise=noise,
    )
    return next(chunks), true


def stream_series(
    key: jax.Array,
    n_frames: int,
    *,
    chunk_size: int = 32,
    size: Size = 96,
    period: float = 12.0,
    drift_step: float | None = None,
    rotation_step: float = 0.002,
    noise: float = 0.25,
) -> Tuple[Iterator[jax.Array], Deformation]:
    """Streaming twin of :func:`make_series`: frames arrive in acquisition
    order as ``(chunk,)`` batches of at most ``chunk_size``.

    Stands in for the paper's parallel-filesystem ingest: the drift
    trajectory is fixed up front (it is metadata-sized), but frames are
    *rendered* lazily per chunk, so a consumer — ``repro.register_series`` —
    can overlap function-A preprocessing with acquisition instead of waiting
    for the full series.  Every frame is rendered by the same one-frame
    program, so frames are bit-identical whatever the chunk size (a batched
    render of a different batch size rounds differently); ``make_series``
    is the single-chunk special case.

    Returns ``(chunks, true)``: the chunk iterator and the ground-truth
    cumulative deformations (for evaluation only — not consumed upstream).
    """
    if drift_step is None:
        drift_step = period * 0.35
    kb, kd, kr, kn = jax.random.split(key, 4)
    base = lattice_image(size, period, kb)
    steps = jax.random.uniform(
        kd, (n_frames, 2), minval=-drift_step, maxval=drift_step
    )
    rots = jax.random.uniform(
        kr, (n_frames,), minval=-rotation_step, maxval=rotation_step
    )
    steps = steps.at[0].set(0.0)
    rots = rots.at[0].set(0.0)
    cum_shift = jnp.cumsum(steps, axis=0)
    cum_rot = jnp.cumsum(rots)
    nkeys = jax.random.split(kn, n_frames)

    def chunks() -> Iterator[jax.Array]:
        for lo in range(0, n_frames, chunk_size):
            hi = min(lo + chunk_size, n_frames)
            yield jnp.stack([
                _render_frame(base, cum_shift[i], cum_rot[i], nkeys[i], noise)
                for i in range(lo, hi)
            ])

    true = {"angle": cum_rot, "shift": cum_shift}
    return chunks(), true
