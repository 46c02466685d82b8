"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Ahead-of-time compiles against a described (not attached) ``v5e:2x2``
chip: the TPU compiler runs, nothing executes.  This catches what
interpret-mode tests cannot — blocks that break the (8, 128) rule, kernel
bodies Mosaic will not lower, blocks that overflow VMEM.  The topology is
described inside a module-scoped fixture (never at import: only one
process may load the TPU library), and every test skips where it cannot be
described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels._tiling import (
    build_round_matrices,
    default_num_tiles,
    pack_leaves,
    packed_op,
    vmem_tiles,
)

N, D = 1 << 20, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _affine():
    """The two-leaf affine operator packed into one (rows, 2D) array."""
    _, spec = pack_leaves((jnp.zeros((1, D)), jnp.zeros((1, D))))
    return packed_op(lambda a, b: (a[0] * b[0], a[1] * b[0] + b[1]), spec)


def test_lookback_scan_compiles(one_chip):
    from repro.kernels.lookback_scan import lookback_scan

    t = default_num_tiles(N, D * 4)
    _compile(
        lambda x: lookback_scan(jnp.add, x, t, interpret=False)[0],
        _spec(one_chip, (N, D)),
    )


def test_lookback_scan_packed_affine_compiles(one_chip):
    """The decoupled backend's program for ``repro.scan(aff, (m, c))``."""
    from repro.kernels.lookback_scan import lookback_scan

    t = default_num_tiles(N, 2 * D * 4)
    _compile(
        lambda x: lookback_scan(_affine(), x, t, interpret=False)[0],
        _spec(one_chip, (N, 2 * D)),
    )


@pytest.mark.parametrize("width", [D, 2 * D])
def test_tile_kernels_compile(one_chip, width):
    from repro.kernels.tile_scan import tile_apply, tile_local_scan

    op = jnp.add if width == D else _affine()
    t = vmem_tiles(N, width * 4)
    k = N // t
    _compile(lambda x: tile_local_scan(op, x, t, interpret=False),
             _spec(one_chip, (N, width)))
    _compile(lambda y, s: tile_apply(op, y, s, interpret=False),
             _spec(one_chip, (t, k, width)), _spec(one_chip, (t, 1, width)))


def test_tile_kernel_over_vmem_is_refused(one_chip):
    """Why tiles are sized by ``vmem_tiles``: 4 MiB blocks overflow."""
    from repro.kernels.tile_scan import tile_local_scan

    t, k = 2, (4 << 20) // (D * 4)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(lambda x: tile_local_scan(jnp.add, x, t, interpret=False)
                ).lower(_spec(one_chip, (t * k, D))).compile()


def test_fused_round_compiles(one_chip):
    from repro.core.engine.plan import get_plan
    from repro.kernels.tile_scan import fused_round

    n = 1024
    plan = get_plan("ladner_fischer", n)
    rnd = max(plan.rounds, key=lambda r: r.num_combines + r.num_moves)
    mats = build_round_matrices(rnd, n)
    live = [i for i, m in enumerate(mats) if m is not None]
    specs = [_spec(one_chip, mats[i].shape) for i in live]

    def step(y, *args):
        full = [None] * len(mats)
        for i, a in zip(live, args):
            full[i] = a
        return fused_round(jnp.add, y, tuple(full), interpret=False)

    _compile(step, _spec(one_chip, (n, D)), *specs)


FRAME = (1920, 1856)  # the paper's frames (PAPER.md)


def test_bilinear_fetch_compiles(one_chip):
    from repro.kernels.bilinear_fetch import bilinear_fetch

    _compile(
        lambda img, r0, c0: bilinear_fetch(img, r0, c0, interpret=False),
        _spec(one_chip, FRAME),
        _spec(one_chip, FRAME, jnp.int32),
        _spec(one_chip, FRAME, jnp.int32),
    )


def test_warp_gradient_step_batch_compiles(one_chip, monkeypatch):
    """Function A's inner step as ``SeriesSession.feed`` batches it: the
    gradient of 1 - NCC through the kernel-backed warp, vmapped over a
    chunk of 8 pairs, fits the chip's 16 GB."""
    from repro.core.deformation import ncc, warp
    from repro.kernels import bilinear_fetch

    # The host is a CPU, where the kernel would be interpreted.
    monkeypatch.setattr(bilinear_fetch, "resolve_interpret", lambda _: False)

    def step(ref, tmpl, d):
        loss = lambda d: 1.0 - ncc(ref, warp(tmpl, d, kernel=True))
        return jax.grad(loss)(d), loss(d)

    frames = _spec(one_chip, (8,) + FRAME)
    d = {"angle": _spec(one_chip, (8,)), "shift": _spec(one_chip, (8, 2))}
    compiled = _compile(jax.vmap(step), frames, frames, d)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_fused_ncc_refused_on_tpu(monkeypatch):
    """``fused=True`` names a kernel that does not lower for the TPU: the
    operator refuses at construction there, not mid-scan."""
    from repro.core import registration
    from repro.core.registration import RegistrationOperator, SeriesRegistrar

    reg = SeriesRegistrar(jnp.asarray(np.zeros((2, 64, 64), np.float32)))
    monkeypatch.setattr(registration.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="does not lower"):
        RegistrationOperator(reg, fused=True)
    assert not RegistrationOperator(reg).fused
