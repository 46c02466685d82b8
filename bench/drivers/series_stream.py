"""Driver: EM series registration through ``open_series``/``feed``/``result``.

Set-up renders ``series_rendered`` series of ``series_frames`` frames on
the device, one jitted call per chunk, then warms every shape the window
uses: one session fed two chunks (a session's first feed registers
``chunk - 1`` pairs and later feeds ``chunk``, so both batched function-A
programs and the unbatched function-B program compile) and its result,
and the stacking of every result length a window can end on.

The window opens one session per series, cycling through the rendered
series, and feeds it chunk by chunk: one closed-loop caller.  It stops
feeding at ``seconds``, finishes the current feed and calls ``result()``.
``frames_per_s`` is the frames whose deformations were delivered over the
time from the window's start to the return of the last ``result()``.

The check compares every delivered deformation with the generator's known
drift (``reference/registration_truth.py``), by the numbers the cell's
limits name (``NUMBERS``).
"""

from __future__ import annotations

import gc
import time
import traceback
from typing import Dict

import numpy as np

from gen.lattice_series import render_frames, series_truth
from reference.registration_truth import frame_errors


#: The numbers a cell's limits file (``bench/limits/<cell>.json``) may
#: compare, each from one per-frame error of every delivered frame: the
#: worst frame's, the 90th percentile's, or the median frame's.  A frame
#: whose answer is not finite, or (where the cell compares the worst
#: frame) is over that limit, counts as failed.
NUMBERS = {
    "displacement_err_px": ("displacement", np.max),
    "displacement_p90_px": ("displacement",
                            lambda err: np.percentile(err, 90)),
    "displacement_median_px": ("displacement", np.median),
}


def series_config(config: Dict):
    import repro
    from repro.core.registration import RegistrationConfig

    return repro.RegisterSeriesConfig(
        registration=RegistrationConfig(**config["registration"]),
        refine=bool(config["refine"]), devices=1)


def _warm_result_stacks(n_frames: int, chunk: int) -> None:
    """The eager stacking ``result()`` does, at every length it can have."""
    import jax
    import jax.numpy as jnp

    for n in range(chunk, n_frames + 1, chunk):
        jax.block_until_ready([
            jnp.stack([jnp.zeros((), jnp.float32)] * n),
            jnp.stack([jnp.zeros((2,), jnp.float32)] * n),
        ])


def setup(cell, seed: int, span, log) -> Dict:
    import jax
    import repro

    cfg, tr = cell.config, cell.traffic
    n, chunk = int(cfg["series_frames"]), int(tr["chunk"])
    truths, chunks = [], []
    t0 = time.perf_counter()
    for s in range(int(tr["series_rendered"])):
        truth = series_truth(seed, s, n, cfg, tr["drift"])
        truths.append(truth)
        chunks.append([render_frames(seed, s, truth, cfg, lo,
                                     min(lo + chunk, n))
                       for lo in range(0, n, chunk)])
    jax.block_until_ready(chunks)
    log(f"render: {len(chunks)} series x {n} frames of "
        f"{cfg['frame_hw'][0]}x{cfg['frame_hw'][1]} in "
        f"{time.perf_counter() - t0:.3f}s")

    scfg = series_config(cfg)
    t0 = time.perf_counter()
    with span("warmup"):
        with repro.open_series(scfg) as sess:
            for ch in chunks[0][:2]:
                sess.feed(ch)
            res = sess.result()
        _warm_result_stacks(n, chunk)
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
    return {
        "metrics": {"frames_per_s": frames / elapsed},
        "counters": {"frames": frames, "frames_fed": frames_fed,
                     "pairs": pairs, "feeds": feeds, "series": len(results),
                     "fn_b_ops": fn_b_ops, "series_failed": failures,
                     "elapsed_s": elapsed},
        "results": results,
    }


def _iteration_spread(iters, chunk: int):
    """Mean over chunks of max/mean function-A iterations in the chunk's
    batch, the per-pair mean, and the largest count."""
    ratios, flat = [], []
    for series in iters:
        flat += series
        # pairs per feed: chunk - 1 for the first, then chunk.
        lo, size = 0, chunk - 1
        while lo < len(series):
            batch = series[lo:lo + size]
            if batch and np.mean(batch) > 0:
                ratios.append(max(batch) / np.mean(batch))
            lo, size = lo + size, chunk
    if not flat:
        return None
    return float(np.mean(ratios)), float(np.mean(flat)), int(max(flat))


def check(state: Dict, window: Dict, log) -> Dict:
    import jax

    delivered = [(s, jax.device_get(d)) for s, d in window["results"]]
    window["results"] = None
    state["chunks"] = None          # free the frames before the reference
    gc.collect()
    hw = tuple(state["cfg"]["frame_hw"])
    parts = {k: [] for k in ("displacement", "shift", "angle")}
    for s, d in delivered:
        truth = state["truths"][s]
        k = int(np.asarray(d["angle"]).shape[0])
        e = frame_errors(d["angle"], d["shift"], truth["angle"][:k],
                         truth["shift"][:k], hw)
        for key in parts:
            parts[key].append(e[key])
    errs = {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in parts.items()}
    for key, err in errs.items():
        if err.size:
            log(f"{key} error: median {float(np.median(err))!r}, "
                f"max {float(err.max())!r} over {err.size} frames")
    numbers = {name: float(stat(errs[key])) if errs[key].size
               else float("inf") for name, (key, stat) in NUMBERS.items()}
    for name, value in numbers.items():
        log(f"number {name} {value!r}")
    limits = state["limits"]
    checks = [(name, numbers[name], float(limit))
              for name, limit in limits.items()]
    disp = errs["displacement"]
    bad = ~np.isfinite(disp)
    if "displacement_err_px" in limits:
        bad |= disp > float(limits["displacement_err_px"])
    counters = window["counters"]
    fed = int(counters["frames_fed"])
    missing = fed - int(disp.size)
    # A series that raised delivered nothing, whether or not it was fed.
    lost = int(counters["series_failed"])
    checks.append(("frames_missing", float(abs(missing)), 0.0))
    checks.append(("series_failed", float(lost), 0.0))
    return {"attempted": fed,
            "failed": int(bad.sum()) + max(missing, 0) + lost,
            "checks": checks}
