"""trace_reduce and the per-layer readers on small traces built by hand."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]


def test_busy_and_gaps_inside_the_window():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 95, 120)]
    window = (10, 100)
    assert tr.busy_ns(ops, window) == 10 + 10 + 5
    assert tr.gaps(ops, window) == [(20, 30), (40, 95)]
    assert tr.gaps([], window) == [window]


def test_seconds_and_counts_by_name_clip_to_the_window():
    ev = [("x", 0, 1_000_000_000), ("x", 2_000_000_000, 2_500_000_000),
          ("y", 900_000_000, 1_100_000_000)]
    got = tr.seconds_by_name(ev, (500_000_000, 3_000_000_000))
    assert got["x"] == pytest.approx(1.0)
    assert got["y"] == pytest.approx(0.2)
    assert tr.count_by_name(ev, (500_000_000, 3_000_000_000)) == {
        "x": 1, "y": 1}


def test_gap_named_by_innermost_span():
    spans = [("window", 0, 100), ("feed", 10, 60), ("inner", 20, 30),
             ("result", 70, 90)]
    assert tr.span_at(spans, 25) == "inner"
    assert tr.span_at(spans, 40) == "feed"
    assert tr.span_at(spans, 95) == "window"
    assert tr.span_at(spans, 150) == "none"


def _trace():
    # Window 0..100 ns; ops in 10..20 (feed), 50..60 (result) and 80..85.
    ops = [("fusion.1", 10, 20), ("fusion.2", 50, 55), ("copy", 55, 60),
           ("fusion.1", 80, 85)]
    modules = [("jit__lambda(3)", 10, 20), ("jit__register_pair(4)", 50, 60),
               ("jit__register_pair(4)", 80, 85)]
    spans = [("window", 0, 100), ("feed", 5, 40), ("result", 45, 70)]
    return tr.Trace(ops=[ops], modules=[modules], spans=spans)


def test_summary_busy_idle_and_breakdown():
    s = tr.summary(_trace())
    assert s["busy_s"] == pytest.approx(25e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_share"] == pytest.approx(0.75)
    assert s["device_ops"][0] == ("fusion.1", pytest.approx(15e-9))
    # Gaps 20-50, 60-80, 85-100, 0-10, named at their midpoints 35, 70,
    # 92 and 5 (a span covers [start, end)).
    assert [(k, round(v * 1e9)) for k, v in s["idle_gaps"]] == [
        ("feed", 30), ("window", 20), ("window", 15), ("feed", 10)]


def test_window_span_must_be_unique():
    t = _trace()
    t.spans.append(("window", 0, 5))
    with pytest.raises(ValueError):
        t.window


def _inp(counters, cell_name="series_drift"):
    cell = run.resolve(run.load_manifest(), cell_name)
    return run.LayerInput(trace=_trace(), counters=counters, cell=cell,
                          peaks=run.peaks_for("TPU v5 lite"))


def test_series_readers():
    inp = _inp({"pairs": 2, "frames": 4, "fn_b_ops": 6})
    assert run.metric_reader("fn_a_device_ms_per_pair")(inp) == \
        pytest.approx(1e3 * 10e-9 / 2)
    assert run.metric_reader("fn_b_device_ms_per_call")(inp) == \
        pytest.approx(1e3 * 15e-9 / 2)
    assert run.metric_reader("fn_b_calls_per_frame")(inp) == 1.5
    assert run.metric_reader("device_idle_share.series")(inp) == \
        pytest.approx(75.0)


def test_readers_return_nothing_when_nothing_to_read():
    inp = _inp({"pairs": 0, "frames": 0, "fn_b_ops": 0})
    inp.trace.modules[0].clear()
    for name in ("fn_a_device_ms_per_pair", "fn_b_device_ms_per_call",
                 "fn_b_calls_per_frame"):
        assert run.metric_reader(name)(inp) is None


def test_ops_named_by_program_and_instruction():
    ops = [("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10, 20),
           ("%copy.1 = f32[2]{0} copy(f32[2]{0} %x)", 30, 35),
           ("%add = f32[] add(f32[] %a, f32[] %b)", 50, 51)]
    mods = [("jit__lambda(1447)", 5, 25), ("jit__register_pair(7)", 28, 40)]
    assert [n for n, _, _ in tr.qualify_ops(ops, mods)] == [
        "jit__lambda/fusion.3", "jit__register_pair/copy.1", "?/add"]
    assert tr.module_name("jit__lambda(1447)") == "jit__lambda"

