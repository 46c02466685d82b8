"""Near-periodic lattice image series with a known drift, made on the device.

After ``repro.data.images.stream_series`` (two cosine gratings and a
diagonal one, a few Gaussian defects, shot noise, rigid drift), with two
changes that make the benchmark's set-up short and its truth exact:

* Every frame is evaluated from the lattice's closed form at the frame's
  coordinates, instead of warping a rendered base frame: no per-pixel
  gather, and a whole series is one jitted program.
* Frame ``k`` is ``f_k(y) = B(phi_k^{-1}(y))`` with the exact inverse of
  the rigid deformation ``phi_k(x) = R(a_k)(x - c) + c + G_k``, so
  ``f_k o phi_k = f_0`` holds exactly (up to noise) and
  ``(a_k, G_k)`` is the true cumulative deformation phi_{0,k}, in the
  program's convention: coordinates are (row, col), ``c`` is the frame's
  centre, angles in radians, shifts in pixels.

The drift is drawn on the host (it is metadata-sized); the frames are
drawn on the device from the seed.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

from .seeding import device_key, host_rng


def drift_steps(rng: np.random.Generator, n_frames: int, period: float,
                drift: Dict) -> Tuple[np.ndarray, np.ndarray]:
    """Per-frame steps ``(angle (n,), shift (n, 2))``; frame 0 takes none.

    ``drift["kind"]``:

    * ``"uniform"``: every step uniform within ``±shift_share * period``
      per axis and ``±rotation`` radians.
    * ``"burst"``: in each group of ``group`` frames one frame steps
      ``±[jump_lo, jump_hi] * period`` per axis (random signs) and
      ``±jump_rotation`` radians, the others ``±calm_share * period`` and
      ``±calm_rotation``.
    """
    kind = drift["kind"]
    if kind == "uniform":
        s = drift["shift_share"] * period
        shift = rng.uniform(-s, s, (n_frames, 2))
        angle = rng.uniform(-drift["rotation"], drift["rotation"], n_frames)
    elif kind == "burst":
        c = drift["calm_share"] * period
        shift = rng.uniform(-c, c, (n_frames, 2))
        angle = rng.uniform(-drift["calm_rotation"], drift["calm_rotation"],
                            n_frames)
        g = int(drift["group"])
        for lo in range(0, n_frames, g):
            j = max(lo, 1)
            mag = rng.uniform(drift["jump_lo"], drift["jump_hi"], 2) * period
            shift[j] = mag * rng.choice([-1.0, 1.0], 2)
            angle[j] = rng.uniform(-drift["jump_rotation"],
                                   drift["jump_rotation"])
    else:
        raise ValueError(f"unknown drift kind {kind!r}")
    shift[0] = 0.0
    angle[0] = 0.0
    return angle, shift


def permute_in_groups(rng: np.random.Generator, n_frames: int,
                      group: int) -> np.ndarray:
    """A permutation of frames ``1 .. n-1`` that keeps each frame inside
    its group ``[lo, lo + group)`` (groups aligned to ``group``); frame 0
    stays first."""
    order = np.arange(n_frames)
    for lo in range(0, n_frames, group):
        idx = np.arange(max(lo, 1), min(lo + group, n_frames))
        order[idx] = rng.permutation(idx)
    return order


def series_truth(seed: int, series: int, n_frames: int, config: Dict,
                 drift: Dict) -> Dict[str, np.ndarray]:
    """The true cumulative deformations of one series, as float32 (the
    values the frames are rendered from), and its defect positions.

    Every seed steps through the same set of steps: they are drawn once
    per series from a fixed stream, and the seed only reorders them
    inside each group of ``drift["group"]`` frames (the chunks the
    driver feeds).  So every seed gives each feed the same work in
    another order; the seed also places the defects and draws the noise.
    With ``drift["steps"] == "seeded"`` the seed draws the steps too (the
    readings that output limits are set from use it; the cells do not).
    """
    h, w = config["frame_hw"]
    period = config["period"]
    steps = drift.get("steps", "fixed")
    if steps not in ("fixed", "seeded"):
        raise ValueError(f"unknown drift steps {steps!r}")
    steps_rng = (host_rng(seed, 3, series) if steps == "seeded"
                 else host_rng(0, 1, series))
    d_angle, d_shift = drift_steps(steps_rng, n_frames, period, drift)
    rng = host_rng(seed, 1, series)
    order = permute_in_groups(rng, n_frames, int(drift["group"]))
    d_angle, d_shift = d_angle[order], d_shift[order]
    n_blobs = config["defects"]
    return {
        "angle": np.cumsum(d_angle).astype(np.float32),
        "shift": np.cumsum(d_shift, axis=0).astype(np.float32),
        "blob_rc": np.stack([rng.uniform(0, h, n_blobs),
                             rng.uniform(0, w, n_blobs)], -1)
                     .astype(np.float32),
    }


def _lattice(r, c, blob_rc, period: float, distortion: float):
    """The lattice's closed form at coordinates (row ``r``, col ``c``)."""
    import jax.numpy as jnp

    two_pi = 2.0 * np.pi
    img = (jnp.cos(two_pi * c / period) + jnp.cos(two_pi * r / period)
           + 0.5 * jnp.cos(two_pi * (c + r) / (period * np.sqrt(2.0))))
    width2 = 2.0 * (period * 0.8) ** 2
    for i in range(blob_rc.shape[0]):
        sign = 1.0 if i % 2 == 0 else -1.0
        img = img + sign * distortion * jnp.exp(
            -((r - blob_rc[i, 0]) ** 2 + (c - blob_rc[i, 1]) ** 2) / width2)
    return img


def _render_frames(key, first, blob_rc, angles, shifts, *, hw, period,
                   distortion, noise):
    import jax
    import jax.numpy as jnp

    h, w = hw
    rows = jnp.arange(h, dtype=jnp.float32)[:, None]
    cols = jnp.arange(w, dtype=jnp.float32)[None, :]
    cr, cc = (h - 1) / 2.0, (w - 1) / 2.0
    base = _lattice(rows, cols, blob_rc, period, distortion)
    mean, std = base.mean(), base.std() + 1e-6

    def frame(args):
        i, a, g = args
        # x = R(-a)(y - c - G) + c, written out elementwise (no matmul, so
        # no reduced-precision pass on any chip).
        ur, uc = rows - cr - g[0], cols - cc - g[1]
        ca, sa = jnp.cos(a), jnp.sin(a)
        xr = ca * ur + sa * uc + cr
        xc = -sa * ur + ca * uc + cc
        img = (_lattice(xr, xc, blob_rc, period, distortion) - mean) / std
        return img + noise * jax.random.normal(
            jax.random.fold_in(key, i), (h, w), jnp.float32)

    idx = first + jnp.arange(angles.shape[0])
    return jax.lax.map(frame, (idx, angles, shifts))


@functools.cache
def _render_program():
    import jax

    return jax.jit(_render_frames,
                   static_argnames=("hw", "period", "distortion", "noise"))


def render_frames(seed: int, series: int, truth: Dict, config: Dict,
                  lo: int, hi: int):
    """Frames ``lo`` to ``hi - 1`` of one series, ``(hi - lo, H, W)``
    float32, in one jitted call.  A frame's noise depends on its index
    alone, so the frames are the same whatever the ranges."""
    import jax.numpy as jnp

    return _render_program()(
        device_key(seed, 2, series), jnp.int32(lo),
        jnp.asarray(truth["blob_rc"]), jnp.asarray(truth["angle"][lo:hi]),
        jnp.asarray(truth["shift"][lo:hi]),
        hw=tuple(config["frame_hw"]), period=float(config["period"]),
        distortion=float(config["distortion"]), noise=float(config["noise"]))
