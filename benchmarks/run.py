"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage:
  PYTHONPATH=src python -m benchmarks.run [--only microbench,...]
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

# Suite name -> module.  Modules are imported only when selected, so a
# suite that starts its own processes (sharded) runs before anything in
# this process has touched a device.
SUITES = {
    "microbench": "bench_microbench",          # paper Fig. 8
    "strong_scaling": "bench_strong_scaling",  # paper Table 3 / Fig. 1 & 9
    "hierarchical": "bench_hierarchical",      # paper Table 4
    "work_energy": "bench_work_energy",        # paper Table 5
    "weak_scaling": "bench_weak_scaling",      # paper Fig. 10
    "operator_cost": "bench_operator_cost",    # paper Fig. 5
    "registration_e2e": "bench_registration_e2e",  # paper Figs. 1/9
    "scan_kernels": "bench_scan_kernels",      # in-model scan paths
    "serve": "bench_serve",                    # resident runtime / sessions
    "slo": "bench_slo",                        # serving tail latency
    "sharded": "bench_sharded",                # multi-device strong scaling
    "roofline": "roofline",                    # dry-run roofline table
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    names = list(SUITES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        t0 = time.time()
        try:
            mod = importlib.import_module(f".{SUITES[name]}", __package__)
            rows = mod.run()
        except Exception:  # noqa: BLE001 — isolate suite failures
            traceback.print_exc()
            failed.append(name)
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.2f},{derived}")
        print(f"# suite {name} done in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
