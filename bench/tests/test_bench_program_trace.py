"""program_trace on the program's events built by hand, beside the device
trace of test_bench_trace_reduce."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import program_trace as pt  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

WINDOW = (0, 100)


def _lanes(t, lanes, useful, issued):
    return (pt.LANES, t, t, {"session": "s", "lanes": lanes,
                             "useful": useful, "issued": issued})


def _trace():
    # As in test_bench_trace_reduce: window 0..100 ns, device busy in
    # 10..20, 50..60 and 80..85.
    ops = [("fusion.1", 10, 20), ("fusion.2", 50, 55), ("copy", 55, 60),
           ("fusion.1", 80, 85)]
    modules = [("jit__lambda(3)", 10, 20), ("jit__register_pair(4)", 50, 60),
               ("jit__register_pair(4)", 80, 85)]
    spans = [("window", 0, 100), ("feed", 5, 40), ("result", 45, 70)]
    return tr.Trace(ops=[ops], modules=[modules], spans=spans)


def _events():
    return [("repro.feed", 6, 39, {"session": "s", "frames": 8}),
            ("repro.fn_a", 8, 38, {"session": "s", "lanes": 2}),
            _lanes(38, 2, 30, 40),
            (pt.FN_B, 61, 65, {"session": "s", "i": 0, "k": 3}),
            (pt.FN_B, 86, 94, {"session": "s", "i": 0, "k": 4}),
            # Outside the window: not read.
            (pt.FN_B, 95, 130, {"session": "s", "i": 0, "k": 5}),
            _lanes(120, 2, 1, 100)]


def test_readings_on_known_events():
    ev = _events()
    assert pt.lane_use(ev, WINDOW) == pytest.approx(75.0)
    assert pt.mean_ms(ev, pt.FN_B, WINDOW) == pytest.approx(1e-6 * 6)


def test_readings_are_none_with_nothing_to_read():
    assert pt.lane_use([], WINDOW) is None
    assert pt.mean_ms([], pt.FN_B, WINDOW) is None
    only_feed = _events()[:2]
    assert pt.lane_use(only_feed, WINDOW) is None
    assert pt.mean_ms(only_feed, pt.FN_B, WINDOW) is None


def test_lane_use_weights_two_levels_by_pixels():
    # Three lanes, iterations (coarse, fine) per lane, pixels 1:4.
    its, pixels = [(10, 2), (20, 4), (30, 6)], (1, 4)
    useful = sum(p * sum(it[lvl] for it in its)
                 for lvl, p in enumerate(pixels))
    issued = sum(p * len(its) * max(it[lvl] for it in its)
                 for lvl, p in enumerate(pixels))
    assert (useful, issued) == (108, 162)
    assert pt.lane_use([_lanes(50, 3, useful, issued)], WINDOW) == \
        pytest.approx(100.0 * 108 / 162)


def test_gap_inside_fn_a_is_named_by_it():
    gaps = pt.named_gaps(_trace(), _events())
    # The 20..50 gap's midpoint 35 lies in repro.fn_a (8..38), inside feed.
    assert ("repro.fn_a", pytest.approx(30e-9)) in gaps
    assert ("feed", pytest.approx(30e-9)) not in gaps
    assert ("feed", pytest.approx(30e-9)) in tr.named_gaps(_trace())


def test_device_readings_unchanged_by_program_spans():
    """With the program's spans among the trace's spans, the device
    readers and the idle share read what they read without them."""
    plain = _trace()
    mixed = _trace()
    mixed.spans += [(n, s, e) for n, s, e, _ in _events()]
    assert mixed.window == plain.window
    assert tr.summary(mixed)["idle_share"] == tr.summary(plain)["idle_share"]
    cell = run.resolve(run.load_manifest(), "series_drift")
    counters = {"pairs": 2, "frames": 4, "fn_b_ops": 6}
    for name in ("device_idle_share.series", "fn_a_device_ms_per_pair",
                 "fn_b_device_ms_per_call", "fn_b_calls_per_frame"):
        read = run.metric_reader(name)
        got = [read(run.LayerInput(trace=t, counters=counters, cell=cell,
                                   peaks=run.peaks_for("TPU v5 lite")))
               for t in (plain, mixed)]
        assert got[0] == got[1] is not None, name
