"""CI cache-effectiveness smoke: a second series must warm-start.

Opens two sessions over the same compile-cache directory and feeds each the
same synthetic series.  The first (cold) session pays the XLA compiles; the
second (warm) one must

* hit the in-process executable cache (``compile_cache["hits"] > 0`` with
  zero new misses), and
* reach its results in <= WARM_RATIO of the cold session's wall time —
  the ISSUE's warm-start first-result latency acceptance bar.

Exit 0 on pass, 1 with a report on fail.  Wall-clock thresholds are only
meaningful because both legs run in one process on one machine seconds
apart — the runner's speed divides out.
"""

from __future__ import annotations

import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.service import RegisterSeriesConfig, open_series

WARM_RATIO = 0.5


def _frames(n: int = 10, size: int = 32) -> jax.Array:
    key = jax.random.PRNGKey(3)
    return jax.random.normal(key, (n, size, size), jnp.float32)


def _run_series(frames, cache_dir, tag: str):
    cfg = RegisterSeriesConfig(refine=False, telemetry_name=f"cache_smoke_{tag}")
    t0 = time.perf_counter()
    with open_series(cfg, compile_cache_dir=cache_dir) as s:
        s.feed(frames[:5])
        s.feed(frames[5:])
        res = s.result()
    return time.perf_counter() - t0, res


def main() -> int:
    frames = _frames()
    failures = []
    # The sessions place their plan store here, and XLA's cache too unless
    # $JAX_COMPILATION_CACHE_DIR is set (then that directory stays the one).
    with tempfile.TemporaryDirectory(prefix="repro_cache_smoke_") as d:
        t_cold, cold = _run_series(frames, d, "cold")
        t_warm, warm = _run_series(frames, d, "warm")
    cc_cold, cc_warm = cold.compile_cache, warm.compile_cache
    print(f"cold: {t_cold:.3f}s  compile_cache={cc_cold}")
    print(f"warm: {t_warm:.3f}s  compile_cache={cc_warm}")
    if not cc_cold or cc_cold.get("misses", 0) < 1:
        failures.append(f"cold session recorded no compile-cache miss: {cc_cold}")
    if not cc_warm or cc_warm.get("hits", 0) < 1:
        failures.append(f"warm session recorded no compile-cache hit: {cc_warm}")
    if cc_warm and cc_warm.get("misses", 0) > 0:
        failures.append(f"warm session recompiled: {cc_warm}")
    if t_warm > WARM_RATIO * t_cold:
        failures.append(
            f"warm wall time {t_warm:.3f}s > {WARM_RATIO} x cold {t_cold:.3f}s"
        )
    if failures:
        print("CACHE SMOKE FAILED")
        for f in failures:
            print(f"  FAIL {f}")
        return 1
    print(
        f"cache smoke OK: warm/cold = {t_warm / t_cold:.2f} "
        f"(bar {WARM_RATIO}), {cc_warm.get('hits', 0):.0f} executable hits"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
