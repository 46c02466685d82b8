"""Readings that the output limits are set from: the program's compared
numbers over many seeds, or a control's, in one process.

    python3 bench/control.py --workload <cell> --mode <mode> \
        --seconds <s> --seeds <n> [<n> ...] [--seeded-steps]

``sound`` runs the cell as the benchmark does (set-up, a window, the
check) once for each seed and prints each seed's compared numbers.  The
other modes first put a control in the program's place:

* ``precision_high`` and ``precision_default``: the program's products
  of rotations with pixel coordinates at ``Precision.HIGH`` (three
  bfloat16 passes) or ``Precision.DEFAULT`` (one bfloat16 pass) in place
  of ``HIGHEST``.  Each first logs how far such a product moves a pixel
  coordinate of the frame on this device.
* ``refine_off``: the program's own ``refine=False`` path, which composes
  function A's pair registrations without function B's re-registration
  against frame 0.  It breaks the configuration's guarantee that every
  frame is registered to frame 0: pair errors accumulate along the
  series.

``--seeded-steps`` lets each seed draw the drift's steps as well, where
the cells only reorder one fixed set.

A control has to come out not correct.  The benchmark's own runs never
run this file.  One JSON line per seed goes to standard output, with
every number the driver can compare (``numbers``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import run


def coordinate_error(precision, hw, angle: float = 0.002) -> float:
    """The largest error, in pixels, of a rotation product of the frame's
    pixel coordinates at ``precision`` on the default device, against
    float64: what the program's ``warp`` computes at that precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    h, w = hw
    grid = np.stack(np.meshgrid(np.arange(h), np.arange(w), indexing="ij"),
                    -1) - np.array([(h - 1) / 2.0, (w - 1) / 2.0])
    rot = np.array([[np.cos(angle), -np.sin(angle)],
                    [np.sin(angle), np.cos(angle)]])
    got = jax.jit(lambda r, x: jnp.einsum("ij,hwj->hwi", r, x,
                                          precision=precision))(
        jnp.asarray(rot, jnp.float32), jnp.asarray(grid, jnp.float32))
    ref = np.einsum("ij,hwj->hwi", rot.astype(np.float32).astype(np.float64),
                    grid.astype(np.float32).astype(np.float64))
    return float(np.abs(np.asarray(got, np.float64) - ref).max())


def _series_precision(level):
    def install(cell, patch):
        import jax

        import repro.core.deformation as deformation

        precision = getattr(jax.lax.Precision, level)
        patch(deformation, "_EXACT", precision)
        err = coordinate_error(precision, tuple(cell.config["frame_hw"]))
        print(f"control: rotation products at {level}: a pixel coordinate "
              f"off by up to {err!r} px", file=sys.stderr, flush=True)
    return install


def _series_refine_off(cell, patch):
    drv = cell.driver
    sound = drv.series_config
    patch(drv, "series_config",
          lambda cfg: dataclasses.replace(sound(cfg), refine=False))


CONTROLS = {
    "series_stream": {"precision_high": _series_precision("HIGH"),
                      "precision_default": _series_precision("DEFAULT"),
                      "refine_off": _series_refine_off},
}


def install_control(cell: run.Cell, mode: str, patch=setattr) -> None:
    """Put control ``mode`` in the program's place for ``cell``'s driver;
    ``patch(obj, name, value)`` sets each attribute (a test passes its
    ``monkeypatch.setattr``, which undoes it)."""
    kinds = CONTROLS[cell.traffic["driver"]]
    if mode not in kinds:
        raise ValueError(f"no control {mode!r} for driver "
                         f"{cell.traffic['driver']!r}: {sorted(kinds)}")
    kinds[mode](cell, patch)


def readings(cell: run.Cell, seeds, seconds: float, device, log):
    """One run of ``cell`` per seed; yields ``(seed, result line, every
    number the driver logged as comparable)``."""
    for seed in seeds:
        numbers = {}

        def note(msg: str) -> None:
            if msg.startswith("number "):
                _, name, value = msg.split()
                numbers[name] = float(value)
            log(msg)

        out = run.run_cell(cell, seed, seconds, False, device, note)
        yield seed, out, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=["sound", "precision_high", "precision_default",
                             "refine_off"])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seeded-steps", action="store_true")
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_manifest(), args.workload)
    if args.seeded_steps:
        cell.traffic = dict(cell.traffic,
                            drift=dict(cell.traffic["drift"], steps="seeded"))

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        device = run.device_check(cell.chips)
    except run.NoChip as e:
        log(f"control: {e}")
        return run.EXIT_NO_CHIP
    if args.mode != "sound":
        install_control(cell, args.mode)
    for seed, out, numbers in readings(cell, args.seeds, args.seconds,
                                       device, log):
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seeded_steps": args.seeded_steps, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "checks": out["checks"],
                          "numbers": numbers, "metrics": out["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
