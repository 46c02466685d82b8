"""The series path's spans and counters in a profiler trace (CPU).

A session runs under ``jax.profiler.trace``; its host events are read back
with the benchmark's ``program_trace.load``, which needs no device plane.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.registration import RegistrationConfig, lane_work, register_pair
from repro.data.images import make_series

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import program_trace  # noqa: E402

CHUNKS, CHUNK = 3, 4
#: Spans of every feed, with or without a dispatch.
EVERY = {"repro.feed", "repro.feed.ingest", "repro.fn_a", "repro.fn_a.lanes",
         "repro.scan", "repro.fn_b", "repro.result"}
PHASES = {"repro.scan.phase1", "repro.scan.phase2", "repro.scan.phase3"}


@pytest.fixture(scope="module")
def frames():
    frames, _ = make_series(jax.random.PRNGKey(3), CHUNKS * CHUNK, size=64,
                            noise=0.1)
    return frames


def _traced(frames, logdir, **cfg):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        with repro.open_series(repro.RegisterSeriesConfig(**cfg)) as s:
            for i in range(CHUNKS):
                s.feed(frames[i * CHUNK:(i + 1) * CHUNK])
            res = s.result()
    return program_trace.load(str(logdir)), s.id, res


def _check(events, sid, res, names):
    assert names <= {n for n, *_ in events}
    for name, _, _, stats in events:
        assert stats.get("session") == sid, name
    scans = [(s, e) for n, s, e, _ in events if n == "repro.scan"]
    fn_b = [(s, e) for n, s, e, _ in events if n == "repro.fn_b"]
    for s, e in fn_b:
        assert any(lo <= s and e <= hi for lo, hi in scans)
    tel = res.op_telemetry
    # The session's one cost prime is recorded as a call without running.
    assert len(fn_b) == tel["calls"] + tel["compile_calls"] - 1
    lanes = [st for n, _, _, st in events if n == "repro.fn_a.lanes"]
    assert sum(st["lanes"] for st in lanes) == CHUNKS * CHUNK - 1
    assert all(0 < st["useful"] <= st["issued"] for st in lanes)
    assert [st["lanes"] for n, _, _, st in events if n == "repro.fn_a"] == \
        [st["lanes"] for st in lanes]
    feeds = [st["frames"] for n, _, _, st in events if n == "repro.feed"]
    assert feeds == [CHUNK] * CHUNKS


def test_session_spans_under_the_dispatcher(frames, tmp_path):
    events, sid, res = _traced(frames, tmp_path)
    _check(events, sid, res, EVERY | {"repro.scan.dispatch"})


@pytest.mark.parametrize("concurrency", [None, 1])
def test_plan_counter_once_per_refined_feed(frames, tmp_path, monkeypatch,
                                            concurrency):
    """``repro.scan.plan`` follows each dispatch with the plan that ran:
    the backend, the worker budget after the operator's concurrency, and
    the elements scanned."""
    from repro.core.registration import RegistrationOperator

    if concurrency is not None:
        monkeypatch.setattr(RegistrationOperator, "op_concurrency",
                            property(lambda self: concurrency))
    events, sid, res = _traced(frames, tmp_path)
    _check(events, sid, res, EVERY | {"repro.scan.dispatch", "repro.scan.plan"})
    plans = [(s, e, st) for n, s, e, st in events if n == "repro.scan.plan"]
    dispatches = [(s, e) for n, s, e, _ in events if n == "repro.scan.dispatch"]
    assert len(plans) == len(dispatches) == CHUNKS
    for (s, _, _), (_, d_end) in zip(plans, dispatches):
        assert s >= d_end
    stats = [st for *_, st in plans]
    assert [st["elements"] for st in stats] == [CHUNK - 1] + [CHUNK] * (CHUNKS - 1)
    assert stats[-1]["backend"] == res.backend
    assert all(st["workers"] >= 1 for st in stats)
    if concurrency == 1:
        assert {st["backend"] for st in stats} == {"element"}
        assert {st["workers"] for st in stats} == {1}


@pytest.mark.parametrize("backend,extra", [
    ("worksteal", {"num_threads": 2}),
    ("hierarchical", {"num_segments": 2, "num_threads": 1}),
])
def test_scan_phase_spans(frames, tmp_path, backend, extra):
    events, sid, res = _traced(frames, tmp_path, backend=backend, **extra)
    _check(events, sid, res, EVERY | PHASES)
    assert "repro.scan.dispatch" not in {n for n, *_ in events}


def test_session_runs_without_a_profiler(frames):
    """Outside a trace the spans are inert and the stages all count."""
    with repro.open_series(repro.RegisterSeriesConfig()) as s:
        s.feed(frames[:CHUNK])
        res = s.result()
    assert res.n_frames == CHUNK
    assert set(res.timings) == {"ingest", "preprocess", "scan", "compose",
                                "compile"}


def test_level_iterations_sum_to_iterations(frames):
    cfg = RegistrationConfig()
    res = register_pair(frames[0], frames[1], None, cfg)
    assert res.level_iterations.shape == (cfg.levels,)
    assert int(res.level_iterations.sum()) == int(res.iterations)
    batch = jax.vmap(lambda r, t: register_pair(r, t, None, cfg))(
        frames[:3], frames[1:4])
    assert batch.level_iterations.shape == (3, cfg.levels)
    np.testing.assert_array_equal(
        np.asarray(batch.level_iterations).sum(axis=1),
        np.asarray(batch.iterations))


def test_lane_work_weights_levels_by_pixels():
    # Two levels of a 4x4 frame: 4 and 16 pixels, coarse to fine (1:4).
    its = jnp.asarray([[10, 2], [20, 4], [30, 6]])
    useful, issued = lane_work(its, (4, 4))
    assert useful == 4 * 60 + 16 * 12
    assert issued == 4 * 3 * 30 + 16 * 3 * 6
    assert lane_work(its[:1], (4, 4)) == (4 * 10 + 16 * 2,) * 2
