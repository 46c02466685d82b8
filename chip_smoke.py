"""Drive the system's two user paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases 1-3 below
    python chip_smoke.py --chips 4    # four chips: the sharded scan only

One process holds the chip for the whole run; nothing is delegated to a
child.  Phases, each of which must pass:

1. Device check: the default device is a TPU, or exit non-zero before any
   work (there is no CPU fallback).
2. Series registration, the paper's application: 16 frames of
   1920 x 1856 float32 (the paper's frame size; the series is cut from
   4,096 frames to 16) fed in two chunks of 8 through
   ``repro.open_series`` -> ``feed`` -> ``result`` with the default
   dispatch, checked against the ground-truth drift and against the plain
   float32 sequential reference ``SeriesRegistrar(frames).sequential()``.
3. Generic scan: ``repro.scan`` of the affine operator over two
   2^20 x 128 float32 operands (cost-model dispatch, which must pick the
   compiled ``decoupled`` kernel), then ``backend="hierarchical"`` (the
   compiled Pallas tile kernels), both against a sequential
   ``jax.lax.scan`` fold on the device.

``--chips 4`` runs ``repro.scan(..., devices=4)``, which dispatches the
``sharded`` backend, checks that its output spans the four devices and
that phase 2 ran the two-round exscan, and compares it with the one-chip
result.

Every line but the last is a report.  The last line of standard output is
a JSON object ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  Seconds are host wall-clock around work that ends in
``block_until_ready``; a first call includes compilation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FRAME_HW = (1920, 1856)       # the paper's frames (PAPER.md)
N_FRAMES = 16
CHUNK = 8
TRUTH_TOL_PX = 0.35           # the bound the registration tests use
REFERENCE_TOL_PX = 0.1
SCAN_N, SCAN_D = 1 << 20, 128
SCAN_REL_TOL = 1e-3           # f32 reference fold over 2^20 steps


class SmokeFailure(RuntimeError):
    """A phase ran but its output missed a check."""


def log(msg: str = "") -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_check(chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"jax {jax.__version__}: {len(devs)} x {d0.platform} "
        f"({d0.device_kind})")
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: the default device is {d0.platform!r}, not a TPU; "
            "this script measures nothing elsewhere"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                         f"found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# --------------------------------------------------------- registration


def phase_registration(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core.registration import SeriesRegistrar
    from repro.data.images import stream_series

    log(f"== series registration: {N_FRAMES} frames of "
        f"{FRAME_HW[0]}x{FRAME_HW[1]} f32, chunks of {CHUNK}")
    chunks, true = stream_series(jax.random.PRNGKey(seed), N_FRAMES,
                                 chunk_size=CHUNK, size=FRAME_HW)
    frames, t_render = timed(lambda: [c for c in chunks])
    log(f"  render: {t_render:.3f}s")

    t0 = time.perf_counter()
    with repro.open_series(repro.RegisterSeriesConfig(devices=1)) as session:
        for chunk in frames:
            session.feed(chunk)
        res = session.result()
    log(f"  open_series/feed/result: {time.perf_counter() - t0:.3f}s")
    for line in res.report().splitlines():
        log("  " + line)
    log(f"  dispatch: {res.dispatch}")
    log(f"  host cores seen by the dispatcher: {os.cpu_count()}")

    ref_elems, t_ref = timed(
        lambda: SeriesRegistrar(jnp.concatenate(frames)).sequential()
    )
    log(f"  sequential reference: {t_ref:.3f}s")
    got = np.asarray(res.deformations["shift"])[1:]
    ref = np.stack([np.asarray(e.deformation["shift"]) for e in ref_elems])
    err_truth = float(np.abs(got - np.asarray(true["shift"])[1:]).max())
    err_ref = float(np.abs(got - ref).max())
    log(f"  max shift error vs truth:     {err_truth:.6f} px "
        f"(limit {TRUTH_TOL_PX})")
    log(f"  max shift error vs reference: {err_ref:.6f} px "
        f"(limit {REFERENCE_TOL_PX})")
    check(got.shape == (N_FRAMES - 1, 2) and np.isfinite(got).all(),
          f"registration output shape {got.shape} or non-finite values")
    check(err_truth <= TRUTH_TOL_PX, f"{err_truth} px from the truth")
    check(err_ref <= REFERENCE_TOL_PX, f"{err_ref} px from the reference")


# ---------------------------------------------------------- generic scan


def affine(a, b):
    """(m, c) composes: x -> m x + c, the README's affine operator."""
    return (a[0] * b[0], a[1] * b[0] + b[1])


def make_operands(seed: int):
    """Mostly-identity slopes with sparse 1.0001 bumps (bounded running
    products over 2^20 steps), standard-normal offsets; made on device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        km, kc = jax.random.split(key)
        bump = jax.random.uniform(km, (SCAN_N, SCAN_D)) < 0.01
        m = jnp.where(bump, 1.0001, 1.0).astype(jnp.float32)
        return m, jax.random.normal(kc, (SCAN_N, SCAN_D), jnp.float32)

    return make(jax.random.PRNGKey(seed))


def sequential_fold(m, c):
    """The plain reference: one ``lax.scan`` step per row, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fold(m, c):
        def step(acc, x):
            y = affine(acc, x)
            return y, y

        _, (ym, yc) = jax.lax.scan(step, (m[0], c[0]), (m[1:], c[1:]))
        return (jnp.concatenate([m[:1], ym]), jnp.concatenate([c[:1], yc]))

    return fold(m, c)


def rel_error(got, ref) -> float:
    import numpy as np

    return max(
        float(np.abs(np.asarray(g) - np.asarray(r)).max()
              / max(float(np.abs(np.asarray(r)).max()), 1e-30))
        for g, r in zip(got, ref)
    )


def kernel_calls(fn, m, c) -> int:
    """Compiled Pallas kernels in ``fn``'s program (0 when interpreted)."""
    import jax

    return jax.jit(fn).lower(m, c).as_text().count("tpu_custom_call")


def run_scan(name: str, fn, m, c):
    """First call (compiles) and a second, timed; returns the output."""
    import repro.core.engine as engine

    _, t_first = timed(lambda: fn(m, c))
    out, t_run = timed(lambda: fn(m, c))
    d = engine.last_dispatch
    log(f"  {name}: backend={d.backend!r} first call {t_first:.3f}s, "
        f"second {t_run:.3f}s")
    log(f"    dispatch: {d.reason}")
    return out, d


def phase_scan(m, c, ref) -> None:
    import repro
    from repro.kernels._tiling import vmem_tiles

    log(f"== generic scan: affine over 2 x ({SCAN_N}, {SCAN_D}) f32")
    dispatched = lambda m, c: repro.scan(affine, (m, c), devices=1)
    out, d = run_scan("repro.scan", dispatched, m, c)
    err = rel_error(out, ref)
    del out
    kernels = kernel_calls(dispatched, m, c)
    log(f"    max relative error vs sequential fold: {err:.3e}; "
        f"compiled kernels in program: {kernels}")
    check(d.backend == "decoupled", f"dispatched {d.backend!r}, not decoupled")
    check(kernels >= 1, "decoupled kernel was not compiled for the chip")
    check(err <= SCAN_REL_TOL, f"decoupled relative error {err}")

    hier = lambda m, c: repro.scan(affine, (m, c), backend="hierarchical",
                                   devices=1)
    tiles = vmem_tiles(SCAN_N, 2 * SCAN_D * 4)
    log(f"  hierarchical tile kernels: >= {tiles} tiles of "
        f"{SCAN_N // tiles} rows")
    out, d = run_scan("hierarchical", hier, m, c)
    err = rel_error(out, ref)
    del out
    kernels = kernel_calls(hier, m, c)
    log(f"    max relative error vs sequential fold: {err:.3e}; "
        f"compiled kernels in program: {kernels}")
    check(kernels >= 2, "hierarchical tile kernels were not compiled")
    check(err <= SCAN_REL_TOL, f"hierarchical relative error {err}")


def phase_sharded(m, c) -> None:
    import repro
    from repro.core.engine import sharded

    log(f"== sharded scan: affine over 2 x ({SCAN_N}, {SCAN_D}) f32, "
        "4 devices vs 1")
    out4, d = run_scan("repro.scan(devices=4)",
                       lambda m, c: repro.scan(affine, (m, c), devices=4),
                       m, c)
    st = sharded.last_stats
    spans = [len(t.sharding.device_set) for t in out4]
    log(f"    stats: devices={st.devices} shard_rows={st.shard_rows} "
        f"halo={st.halo} phase2={st.phase2_algorithm}/{st.phase2_rounds} "
        f"rounds, cross_steals={st.cross_steals} forced={st.forced_blocks}, "
        f"phases " + ", ".join(f"{k}={v:.3f}s"
                               for k, v in st.phase_seconds.items()))
    log(f"    output spans {spans} devices")
    check(d.backend == "sharded", f"dispatched {d.backend!r}, not sharded")
    check(all(s == 4 for s in spans), f"output spans {spans} devices")
    check(st.devices == 4 and st.phase2_algorithm == "exscan"
          and st.phase2_rounds == 2, f"sharded stats {st}")
    out1, d1 = run_scan("repro.scan(devices=1)",
                        lambda m, c: repro.scan(affine, (m, c), devices=1),
                        m, c)
    err = rel_error(out4, out1)
    log(f"    max relative difference 4 chips vs 1 ({d1.backend}): {err:.3e}")
    check(err <= SCAN_REL_TOL, f"4-chip result differs from 1-chip by {err}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = device_check(args.chips)
    from repro.runtime.compile_cache import enable_persistent_cache

    log(f"compile cache: {enable_persistent_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        m, c = make_operands(args.seed)
        phase_sharded(m, c)
    else:
        phase_registration(args.seed)
        m, c = make_operands(args.seed)
        ref, t_ref = timed(lambda: sequential_fold(m, c))
        log(f"sequential fold reference: {t_ref:.3f}s")
        phase_scan(m, c, ref)
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
