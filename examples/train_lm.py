"""End-to-end training driver: a ~100M-parameter LM with the full substrate
(sharded step, deterministic pipeline, checkpointing, fault recovery).

  PYTHONPATH=src python examples/train_lm.py --steps 20          # quick demo
  PYTHONPATH=src python examples/train_lm.py --steps 300         # real run

The architecture is an xLSTM-family stack (the paper's scan machinery runs
inside every mLSTM block: Pallas-able chunked SSD = reduce-then-scan).
"""

import argparse

import numpy as np

from repro.launch.train import TrainConfig, train
from repro.models.config import ArchConfig
from repro.runtime.compile_cache import enable_persistent_cache

# ~100M params: embed 2*32k*512 = 33M + 16 blocks ~ 4M = ~97M.
ARCH_100M = ArchConfig(
    name="demo-100m",
    family="ssm",
    n_layers=16,
    d_model=512,
    n_heads=4,
    n_kv_heads=4,
    d_ff=0,
    vocab_size=32000,
    block_pattern=("mlstm", "mlstm", "mlstm", "slstm"),
    param_dtype="float32",
    compute_dtype="float32",
)


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a node failure at this step (recovery demo)")
    args = ap.parse_args()

    import repro.configs.xlstm_350m as x350

    x350.SMOKE = ARCH_100M  # route the driver to the demo config

    out = train(TrainConfig(
        arch="xlstm-350m", smoke=True, steps=args.steps, batch=args.batch,
        seq_len=args.seq_len, lr=1e-3, ckpt_dir="/tmp/repro_demo_ckpt",
        save_every=max(10, args.steps // 4),
        fail_at=(args.fail_at,) if args.fail_at else (),
        log_every=5,
    ))
    losses = out["losses"]
    print(f"\ntrained demo-100m for {out['steps']} steps "
          f"(restarts={out['restarts']})")
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(mean step {out['mean_step_s']:.2f}s, "
          f"{args.batch * args.seq_len / out['mean_step_s']:.0f} tok/s)")
    assert np.isfinite(losses[-1])


if __name__ == "__main__":
    main()
