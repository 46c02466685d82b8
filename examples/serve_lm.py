"""Batched serving demo: prefill + step-locked decode with greedy sampling.

  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-7b --requests 4
"""

import argparse

import numpy as np

from repro.launch.serve import Request, ServeConfig, Server
from repro.runtime.compile_cache import enable_persistent_cache


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-7b",
                    help="any of the 10 assigned archs (reduced config)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    srv = Server(ServeConfig(arch=args.arch, smoke=True,
                             max_batch=args.requests))
    print(f"serving {args.arch} (reduced config, "
          f"{sum(x.size for x in __import__('jax').tree.leaves(srv.params)) / 1e6:.1f}M params)")
    rng = np.random.default_rng(7)
    reqs = [
        Request(i, rng.integers(2, srv.acfg.vocab_size, args.prompt_len,
                                dtype=np.int32), max_new=args.max_new)
        for i in range(args.requests)
    ]
    stats = srv.serve_batch(reqs)
    print(f"batch={stats['batch']}  prefill={stats['prefill_s'] * 1e3:.0f}ms  "
          f"decode={stats['decode_s'] * 1e3:.0f}ms  "
          f"throughput={stats['tokens_per_s']:.1f} tok/s")
    for r in reqs:
        print(f"  request {r.rid}: prompt[{len(r.prompt)}] -> {r.output[:10]}...")


if __name__ == "__main__":
    main()
