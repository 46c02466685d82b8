"""Judge delivered registrations by what they say, against the known drift.

Three numbers per frame:

* ``displacement``: the worst displacement error over the frame (below),
  the one the cells compare;
* ``shift``: the error at the frame's centre, |G - G_true| in pixels, which
  a rigid deformation moves by its shift alone;
* ``angle``: |a - a_true| in milliradians.

A series registration answers, for every frame ``k``, the rigid
deformation phi_{0,k} with ``f_k o phi_{0,k} = f_0``.  The generator
(``gen/lattice_series.py``) renders frame ``k`` from the exact inverse of a
known phi_k, so the exact answer is known without any code under test.

The worst displacement error over the frame is the largest distance, over the frame's pixels, between where the delivered
deformation and the true one send a pixel.  For rigid deformations it lies
at one of the four corners.  It counts a rotation error at the frame's
edge, where it moves pixels most, as well as a shift error.  Plain NumPy
in float64; imports nothing of the program.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def _apply(angle, shift, pts, ctr):
    """phi(x) = R(a)(x - c) + c + G for angles (n,), shifts (n, 2) and
    points (p, 2) in (row, col): returns (n, p, 2)."""
    ca, sa = np.cos(angle)[:, None], np.sin(angle)[:, None]
    u = pts[None, :, :] - ctr
    r = ca * u[..., 0] - sa * u[..., 1]
    c = sa * u[..., 0] + ca * u[..., 1]
    return np.stack([r, c], -1) + ctr + shift[:, None, :]


def displacement_error(angle, shift, true_angle, true_shift,
                       hw: Tuple[int, int]) -> np.ndarray:
    """Per-frame worst displacement error in pixels, shape (n,)."""
    h, w = hw
    ctr = np.array([(h - 1) / 2.0, (w - 1) / 2.0])
    corners = np.array([[0, 0], [0, w - 1], [h - 1, 0], [h - 1, w - 1]],
                       np.float64)
    got = _apply(np.asarray(angle, np.float64),
                 np.asarray(shift, np.float64), corners, ctr)
    ref = _apply(np.asarray(true_angle, np.float64),
                 np.asarray(true_shift, np.float64), corners, ctr)
    err = np.linalg.norm(got - ref, axis=-1).max(axis=1)
    finite = np.isfinite(got).all(axis=(1, 2))
    return np.where(finite, err, np.inf)


def frame_errors(angle, shift, true_angle, true_shift,
                 hw: Tuple[int, int]) -> dict:
    """Per-frame ``displacement`` and ``shift`` errors (px) and ``angle``
    error (mrad), each of shape (n,); ``inf`` where an answer is not
    finite."""
    angle = np.asarray(angle, np.float64)
    shift = np.asarray(shift, np.float64)
    d_shift = np.linalg.norm(shift - np.asarray(true_shift, np.float64),
                             axis=-1)
    d_angle = 1e3 * np.abs(angle - np.asarray(true_angle, np.float64))
    return {
        "displacement": displacement_error(angle, shift, true_angle,
                                           true_shift, hw),
        "shift": np.where(np.isfinite(d_shift), d_shift, np.inf),
        "angle": np.where(np.isfinite(d_angle), d_angle, np.inf),
    }
