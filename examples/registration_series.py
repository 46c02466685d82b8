"""The paper's application end-to-end: TEM series registration as a prefix
scan with work stealing (paper §2.3/§3/§5 'scan' and 'full' registration),
driven through the public ``repro.register_series`` pipeline.

  PYTHONPATH=src python examples/registration_series.py [--frames 24]
      [--backend hierarchical --segments 4 --threads 2] [--stream]
"""

import argparse
import time

import jax
import numpy as np

import repro
from repro.core.registration import SeriesRegistrar
from repro.data.images import make_series, stream_series
from repro.runtime.compile_cache import enable_persistent_cache


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=24)
    ap.add_argument("--backend", default=None,
                    help="engine backend (default: cost-model dispatch); "
                         "e.g. hierarchical, worksteal, element")
    ap.add_argument("--segments", type=int, default=None)
    ap.add_argument("--threads", type=int, default=None)
    ap.add_argument("--size", type=int, default=96)
    ap.add_argument("--stream", action="store_true",
                    help="feed frames through the streaming-ingest path")
    args = ap.parse_args()

    print(f"generating {args.frames} near-periodic frames "
          f"({args.size}x{args.size}, drifting lattice + shot noise)...")
    key = jax.random.PRNGKey(0)
    frames, true = make_series(key, args.frames, size=args.size, noise=0.15)

    # --- serial baseline (the paper's reference)
    reg_seq = SeriesRegistrar(frames)
    t0 = time.time()
    elems = reg_seq.preprocess_vmapped()      # function A, batched (parallel)
    seq = reg_seq.sequential(list(elems))
    t_seq = time.time() - t0
    print(f"sequential registration loop: {t_seq:.2f}s "
          f"({reg_seq.op_calls} operator calls, "
          f"{reg_seq.total_iters} minimiser iterations)")

    # --- the pipeline: scan through the engine (hierarchical/worksteal/...)
    cfg = repro.RegisterSeriesConfig(
        backend=args.backend,
        num_segments=args.segments,
        num_threads=args.threads,
    )
    if args.stream:
        src, _ = stream_series(key, args.frames, chunk_size=8,
                               size=args.size, noise=0.15)
    else:
        src = frames
    res = repro.register_series(src, cfg)
    print(res.report())

    est = np.asarray(res.deformations["shift"])[1:]
    tru = np.asarray(true["shift"][1:])
    err = np.abs(est - tru).max()
    agree = max(
        np.abs(np.asarray(a.deformation["shift"])
               - np.asarray(b.deformation["shift"])).max()
        for a, b in zip(seq, res.elements)
    )
    print(f"max drift-recovery error vs ground truth: {err:.3f} px")
    print(f"max |scan - sequential| deformation diff: {agree:.4f} px "
          f"(equivalent minima, paper §2.3.3)")
    print(f"note: the operator is compute-bound; on one CPU the scan's extra "
          f"work costs wall-time — the win appears at P >> 1 "
          f"(benchmarks/bench_registration_e2e.py shows it on controlled "
          f"cost profiles; bench_strong_scaling.py simulates Piz Daint scale).")


if __name__ == "__main__":
    main()
