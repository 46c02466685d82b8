"""The controls that the output limits are set against, and the readings'
drift steps.  ``refine_off`` comes out not correct on tiny cells; the
precision controls put a lower precision in the program's place (their
readings on the chip are in PERF.md: a CPU computes float32 products in
full at any precision)."""

import numpy as np
import pytest

from bench_tiny import run_tiny, tiny_cell

import control  # noqa: E402  (bench_tiny puts bench/ on the path)
from gen.lattice_series import series_truth  # noqa: E402


@pytest.mark.parametrize("name", ["series_drift", "series_burst"])
def test_series_control_is_not_correct(monkeypatch, name):
    cell = tiny_cell(name)
    cell.config = dict(cell.config, series_frames=48)
    control.install_control(cell, "refine_off", monkeypatch.setattr)
    out, lines = run_tiny(cell, seconds=3.0)
    assert not out["correct"], "\n".join(lines)


@pytest.mark.parametrize("level", ["HIGH", "DEFAULT"])
def test_precision_control_patches_the_program(monkeypatch, level):
    import jax

    import repro.core.deformation as deformation

    control.install_control(tiny_cell("series_drift"),
                            "precision_" + level.lower(),
                            monkeypatch.setattr)
    assert deformation._EXACT == getattr(jax.lax.Precision, level)


def test_coordinate_error_is_exact_at_highest():
    import jax

    err = control.coordinate_error(jax.lax.Precision.HIGHEST, (64, 48))
    assert 0 <= err < 1e-3


def test_unknown_control_is_refused():
    with pytest.raises(ValueError):
        control.install_control(tiny_cell("series_drift"), "precision")


@pytest.mark.parametrize("name", ["series_drift", "series_burst"])
def test_steps_fixed_for_the_cells_and_seeded_for_readings(name):
    cell = tiny_cell(name)
    cfg, drift = cell.config, cell.traffic["drift"]

    def steps(seed, kind):
        truth = series_truth(seed, 0, 48, cfg, dict(drift, steps=kind))
        return np.diff(truth["shift"], axis=0)

    def same_set(a, b):
        return np.allclose(np.sort(a, axis=0), np.sort(b, axis=0), atol=1e-4)

    assert same_set(steps(1, "fixed"), steps(2**40, "fixed"))
    assert not same_set(steps(1, "seeded"), steps(2**40, "seeded"))
    assert np.array_equal(steps(5, "seeded"), steps(5, "seeded"))
    with pytest.raises(ValueError):
        steps(1, "other")
