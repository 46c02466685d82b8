"""Cells of the benchmark cut to sizes a CPU test can run, driven by
``run.run_cell`` without the harness's look for a chip."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
#: 96 x 96 frames of ``series_drift`` register to 0.14-0.17 px of the
#: truth in the median frame, 0.21-0.26 px at the 90th percentile and
#: 0.24-0.33 px in the worst; ``refine=False`` reads 0.52-0.74 px,
#: 0.96-1.29 px and 1.2-1.7 px (CPU, 3 seeds, 16 and 48 frames), so the
#: tiny series cells are held to these limits.
TINY_LIMITS = {"displacement_err_px": 1.0, "displacement_p90_px": 0.5,
               "displacement_median_px": 0.3}


def tiny_cell(name: str) -> run.Cell:
    cell = run.resolve(run.load_manifest(), name)
    cell.config = dict(cell.config, frame_hw=[96, 96], series_frames=16)
    cell.limits = {k: TINY_LIMITS[k] for k in cell.limits}
    return cell


def run_tiny(cell: run.Cell, seed: int = 7, seconds: float = 0.5):
    lines = []
    out = run.run_cell(cell, seed, seconds, False, CPU, lines.append)
    return out, lines
