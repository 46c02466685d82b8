"""Driver: one EM series across the chips of one host, through
``open_series``/``feed``/``result`` with the configuration's ``devices``.

As ``series_stream``, with three differences:

* Each chunk is rendered split by frame over the chips, ``chunk / chips``
  consecutive frames on each, every chip drawing its own frames: the
  layout a detector feeding several chips would hand the session.
* The warm-up feeds ``warmup_chunks`` chunks (a series' first feed and
  seeded ones after it), stacks deformations on the first chip at every
  length a window's ``result()`` can have, and runs function B's ``register_pair`` and the deformation
  composition on every chip: a program runs on each chip's own
  executable, and a chip no warm-up feed happened to use would compile
  inside the window.
* The window's counters add the session's cross-chip traffic where the
  program reports it (``SeriesSession.traffic``): steals and the frame
  bytes function B moved between chips.

The check is ``series_stream``'s: every delivered deformation against the
generator's known drift.
"""

from __future__ import annotations

import time
import traceback
from typing import Dict

from drivers.series_stream import (  # noqa: F401  (check is this driver's)
    _iteration_spread,
    check,
)
from gen.lattice_series import render_frames, series_truth


def series_config(config: Dict):
    import repro
    from repro.core.registration import RegistrationConfig

    return repro.RegisterSeriesConfig(
        registration=RegistrationConfig(**config["registration"]),
        refine=bool(config["refine"]), devices=int(config["devices"]))


def frame_sharding(chips: int):
    """Frames split along the first axis over the first ``chips`` devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("frames",))
    return NamedSharding(mesh, PartitionSpec("frames"))


def render_chunk(seed: int, series: int, truth: Dict, config: Dict,
                 lo: int, hi: int, sharding):
    """Frames ``lo`` to ``hi - 1`` as one array split by frame over the
    sharding's devices, each device rendering its own block."""
    import jax

    devs = list(sharding.mesh.devices.flat)
    per = (hi - lo) // len(devs)
    parts = []
    for c, dev in enumerate(devs):
        with jax.default_device(dev):
            parts.append(render_frames(seed, series, truth, config,
                                       lo + c * per, lo + (c + 1) * per))
    shape = (hi - lo,) + tuple(config["frame_hw"])
    return jax.make_array_from_single_device_arrays(shape, sharding, parts)


def _warm_every_chip(chunk, cfg) -> None:
    """Function B's pair registration and composition on each chip, from
    a frame that lives there."""
    import jax

    from repro.core.deformation import compose, identity_deformation
    from repro.core.registration import register_pair

    for shard in chunk.addressable_shards:
        frame = shard.data[0]
        d = jax.device_put(identity_deformation(), shard.device)
        res = register_pair(frame, frame, compose(d, d), cfg)
        jax.block_until_ready(res.deformation)


def _warm_result_stacks(device, n_frames: int, chunk: int) -> None:
    """The eager stacking ``result()`` does, at every length it can have,
    of deformations gathered on ``device`` (the session's first chip)."""
    import jax
    import jax.numpy as jnp

    angle, shift = jax.device_put((jnp.zeros((), jnp.float32),
                                   jnp.zeros((2,), jnp.float32)), device)
    for n in range(chunk, n_frames + 1, chunk):
        jax.block_until_ready([jnp.stack([angle] * n),
                               jnp.stack([shift] * n)])


def setup(cell, seed: int, span, log) -> Dict:
    import jax
    import repro

    cfg, tr = cell.config, cell.traffic
    n, chunk = int(cfg["series_frames"]), int(tr["chunk"])
    chips = int(cfg["devices"])
    if n % chunk or chunk % chips:
        raise ValueError(f"{n} frames in chunks of {chunk} over "
                         f"{chips} chips do not divide evenly")
    sharding = frame_sharding(chips)
    truths, chunks = [], []
    t0 = time.perf_counter()
    for s in range(int(tr["series_rendered"])):
        truth = series_truth(seed, s, n, cfg, tr["drift"])
        truths.append(truth)
        chunks.append([render_chunk(seed, s, truth, cfg, lo, lo + chunk,
                                    sharding)
                       for lo in range(0, n, chunk)])
    jax.block_until_ready(chunks)
    log(f"render: {len(chunks)} series x {n} frames of "
        f"{cfg['frame_hw'][0]}x{cfg['frame_hw'][1]} over {chips} "
        f"chips in {time.perf_counter() - t0:.3f}s")

    scfg = series_config(cfg)
    t0 = time.perf_counter()
    with span("warmup"):
        with repro.open_series(scfg) as sess:
            for ch in chunks[0][:int(tr["warmup_chunks"])]:
                sess.feed(ch)
            res = sess.result()
        _warm_result_stacks(sharding.mesh.devices.flat[0], n, chunk)
        _warm_every_chip(chunks[0][0], scfg.registration)
    log(f"warm-up: {time.perf_counter() - t0:.3f}s, dispatch "
        f"{res.backend}: {res.dispatch.reason if res.dispatch else '-'}")
    log("warm-up stages: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in res.timings.items()))
    return {"cfg": cfg, "traffic": tr, "limits": cell.limits, "scfg": scfg,
            "truths": truths, "chunks": chunks}


def window(state: Dict, seconds: float, span, log) -> Dict:
    import repro

    chunks, scfg = state["chunks"], state["scfg"]
    results, iters = [], []
    frames_fed = pairs = feeds = fn_b_ops = failures = 0
    traffic: Dict[str, int] = {}
    s = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        fed = 0
        try:
            with repro.open_series(scfg) as sess:
                for ch in chunks[s]:
                    if time.perf_counter() >= deadline:
                        break
                    with span("feed"):
                        sess.feed(ch)
                    pairs += ch.shape[0] - (0 if fed else 1)
                    fed += ch.shape[0]
                    feeds += 1
                if fed:
                    with span("result"):
                        res = sess.result()
                    results.append((s, res.deformations))
                    tel = res.op_telemetry
                    fn_b_ops += tel["calls"] + tel["compile_calls"]
                    iters.append(list(getattr(sess, "_pair_iters", [])))
                    report = getattr(sess, "traffic", None)
                    for k, v in (report() if report else {}).items():
                        traffic[k] = traffic.get(k, 0) + v
        except Exception:  # noqa: BLE001  an answer that never comes
            failures += 1
            if failures == 1:
                log("series failed:\n" + traceback.format_exc())
        frames_fed += fed
        s = (s + 1) % len(chunks)
    elapsed = time.perf_counter() - t0
    frames = sum(int(d["angle"].shape[0]) for _, d in results)
    log(f"window: {frames} frames in {len(results)} series, {feeds} feeds, "
        f"{elapsed:.3f}s")
    spread = _iteration_spread(iters, int(state["traffic"]["chunk"]))
    if spread is not None:
        log(f"function-A iterations per pair: max/mean per chunk "
            f"{spread[0]:.3f} (mean over chunks), per-pair mean "
            f"{spread[1]:.1f}, max {spread[2]}")
    counters = {"frames": frames, "frames_fed": frames_fed, "pairs": pairs,
                "feeds": feeds, "series": len(results), "fn_b_ops": fn_b_ops,
                "series_failed": failures, "elapsed_s": elapsed}
    if traffic:
        counters.update(fn_b_steals=traffic["steals"],
                        fn_b_move_bytes=traffic["moved_bytes"],
                        placed_bytes=traffic["placed_bytes"])
    return {
        "metrics": {"frames_per_s": frames / elapsed},
        "counters": counters,
        "results": results,
    }
