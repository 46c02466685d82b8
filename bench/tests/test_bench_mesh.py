"""The four-chip cell on the CPU: its driver on four virtual devices at a
tiny size, and its per-layer readers on a four-plane trace built by hand
and on the driver's counters."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "series_drift_4chip"
MS = 1_000_000

TINY = r"""
import json, sys
sys.path.insert(0, %r)
from bench_tiny import run, tiny_cell, CPU
cell = tiny_cell(%r)
# Series of 24 frames fed 8 at a time: 2 frames a device per chunk.
cell.config = dict(cell.config, series_frames=24)
cell.traffic = dict(cell.traffic, chunk=8)
lines = []
out = run.run_cell(cell, 2**33 + 5, 2.0, False, dict(CPU, count=4),
                   lines.append)
print(json.dumps({"out": out, "lines": lines}))
"""


CONTROL = r"""
import json, sys
sys.path.insert(0, %r)
from bench_tiny import run, tiny_cell, CPU
import control
cell = tiny_cell(%r)
cell.config = dict(cell.config, series_frames=48)
cell.traffic = dict(cell.traffic, chunk=8)
# The control patches the driver's series_config(config), as it does
# series_stream's.
control.CONTROLS[cell.traffic["driver"]] = control.CONTROLS["series_stream"]
control.install_control(cell, "refine_off")
lines = []
out = run.run_cell(cell, 2**33 + 5, 3.0, False, dict(CPU, count=4),
                   lines.append)
print(json.dumps({"out": out, "lines": lines}))
"""


def _on_four_devices(code: str) -> str:
    """Run ``code`` in a child with four virtual CPU devices; its stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(BENCH), "src"),
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


def test_tiny_cell_on_four_devices_is_correct():
    got = json.loads(_on_four_devices(
        TINY % (os.path.join(BENCH, "tests"), CELL)).splitlines()[-1])
    out, lines = got["out"], got["lines"]
    assert out["correct"], "\n".join(lines)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "compiles 0" in next(x for x in lines if "inside the window" in x)
    assert "traces 0" in next(x for x in lines if "inside the window" in x)
    counters = {x.split()[1]: float(x.split()[2]) for x in lines
                if x.startswith("counter ")}
    assert counters["series_failed"] == 0
    assert {"fn_b_steals", "fn_b_move_bytes", "placed_bytes"} <= set(counters)


def test_refine_off_control_is_not_correct_on_four_devices():
    """The limits' control fits this cell's driver: ``refine_off`` over
    four devices drifts past the tiny limits."""
    got = json.loads(_on_four_devices(
        CONTROL % (os.path.join(BENCH, "tests"), CELL)).splitlines()[-1])
    out, lines = got["out"], got["lines"]
    assert out["attempted"] > 0, "\n".join(lines)
    assert not out["correct"], "\n".join(lines)


def _trace():
    """Four device planes in a 100 ms window: chip 0 busy 80 ms (function
    A 60, function B 20 in two executions), chips 1-3 busy 40 ms each
    (function A 30, function B 10 in one execution)."""
    ops, modules = [], []
    for chip in range(4):
        a = 60 if chip == 0 else 30
        b = [(a, a + 10), (a + 10, a + 20)] if chip == 0 else [(a, a + 10)]
        mods = [("jit_fn_a_shards(1)", 0, a * MS)] + [
            ("jit__register_pair(2)", s * MS, e * MS) for s, e in b]
        modules.append(mods)
        ops.append([(m.split("(")[0] + "/op", s, e) for m, s, e in mods])
    return tr.Trace(ops=ops, modules=modules,
                    spans=[("window", 0, 100 * MS)])


def _input(counters):
    cell = run.resolve(run.load_manifest(), CELL)
    return run.LayerInput(trace=_trace(), counters=counters, cell=cell,
                          peaks={})


COUNTERS = {"frames": 64, "pairs": 63, "fn_b_ops": 130, "fn_b_steals": 8,
            "fn_b_move_bytes": 32_000_000}


@pytest.mark.parametrize("metric,want", [
    ("chip_busy_imbalance", 80 / 50),
    ("fn_a_chip_ms_per_pair", (60 + 3 * 30) / 63),
    ("fn_b_chip_ms_per_call", (20 + 3 * 10) / 5),
    ("fn_b_steals_per_frame", 8 / 64),
    ("fn_b_move_mb_per_frame", 32 / 64),
    ("device_idle_share.series", 100 * (1 - 50 / 100)),
    ("fn_b_calls_per_frame", 130 / 64),
])
def test_readers_on_four_planes(metric, want):
    assert run.metric_reader(metric)(_input(COUNTERS)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["fn_b_steals_per_frame",
                                    "fn_b_move_mb_per_frame"])
def test_program_counters_absent_read_nothing(metric):
    """A program that reports no cross-chip traffic (the counters are
    missing) gives no value, and no error."""
    counters = {k: v for k, v in COUNTERS.items() if not k.startswith(
        ("fn_b_steals", "fn_b_move"))}
    assert run.metric_reader(metric)(_input(counters)) is None


def test_cell_resolves_with_its_metrics():
    cell = run.resolve(run.load_manifest(), CELL)
    assert cell.chips == 4 and cell.traffic["driver"] == "series_stream_mesh"
    assert cell.config["devices"] == cell.chips
    names = {m["name"] for m in cell.per_layer}
    assert {"chip_busy_imbalance", "fn_a_chip_ms_per_pair",
            "fn_b_chip_ms_per_call", "fn_b_steals_per_frame",
            "fn_b_move_mb_per_frame", "device_idle_share.series",
            "fn_b_calls_per_frame"} == names
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                    "setup_s"}
