"""One series across four devices (virtual CPU devices, one subprocess each).

``RegisterSeriesConfig(devices=4)`` splits each chunk by frame over the
devices: function A runs sharded by pair, and function B's hierarchical
scan gives each device one segment and one worker.  The deformations are
held to the plain sequential reference on one device within
``TOL_PX``: the hierarchical scan starts some of function B's
registrations from other guesses than the chain does (a segment's base
comes from the chain over segment totals), and the data-dependent stop
(``|Delta D| < tol``) ends each one somewhere inside the flat bottom of
its basin, so the results agree to a few thousandths of a pixel, not to
rounding (ROADMAP D1: another function-A cohort already differs by
3.3e-3 px).  Both sides are 0.1 px from the truth.
"""

import os

TOL_PX = 2e-2

PRELUDE = r"""
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.core.registration import (
    RegistrationConfig, RegistrationOperator, SeriesRegistrar, register_pair)
from repro.data.images import make_series

assert jax.device_count() == 4
TOL_PX = %r
frames, true = make_series(jax.random.PRNGKey(11), 24, size=64, noise=0.12)

def sequential_shift():
    elems = SeriesRegistrar(frames, RegistrationConfig()).sequential()
    return np.stack([np.zeros(2)] + [np.asarray(e.deformation["shift"])
                                     for e in elems])

def run(devices=4):
    with repro.open_series(repro.RegisterSeriesConfig(devices=devices)) as s:
        plans = []
        for i in range(0, 24, 8):
            s.feed(frames[i:i + 8])
            plans.append((s._dispatch.backend, s._dispatch.num_segments,
                          s._dispatch.num_threads))
        res = s.result()
        return s, res, plans, s.traffic()
""" % TOL_PX


FOUR = PRELUDE + r"""
s, res, plans, traffic = run()
# Seeded feeds of 8 elements: one segment and one worker per device.
assert plans[1:] == [("hierarchical", 4, 1)] * 2, plans
st = res.scan_stats
assert len(st.segment_bounds) == 4 and st.threads_per_segment == 1
# Function B ran on every device.
chips = {next(iter(e.deformation["shift"].devices())) for e in res.elements}
assert len(chips) == 4, chips
got = np.asarray(res.deformations["shift"])
diff = np.abs(got - sequential_shift()).max()
assert diff < TOL_PX, diff
assert np.abs(got - np.asarray(true["shift"])).max() < 0.3
# Placement: 3 chunks laid out, 3 + 4 + 4 halo frames, frame 0 on 3 chips.
frame = 64 * 64 * 4
assert traffic["placed_bytes"] == (3 * 8 + 11 + 3) * frame, traffic
print("FOUR_OK", diff)
"""


def test_four_device_session_matches_sequential(subproc):
    out = subproc(FOUR, devices=4, timeout=300)
    assert "FOUR_OK" in out


SHARDED_A = PRELUDE + r"""
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.service import _fn_a_shards, _shard_rows
cfg = RegistrationConfig()
mesh = Mesh(np.asarray(jax.devices()), ("frames",))
sh = NamedSharding(mesh, P("frames"))
chunk = jax.device_put(frames[8:16], sh)
# Each device's halo: the frame before its first.
halo = jax.device_put(jnp.stack([frames[7], frames[9], frames[11],
                                 frames[13]]), sh)
res = jax.jit(_fn_a_shards(mesh, cfg))(halo, chunk)
assert len(res.deformation["shift"].sharding.device_set) == 4
rows = _shard_rows(res.deformation["shift"])
assert [next(iter(r.devices())) for r in rows] == jax.devices()
got = np.asarray(res.deformation["shift"])
for p in range(8):
    one = register_pair(frames[7 + p], frames[8 + p], None, cfg)
    d = np.abs(np.asarray(one.deformation["shift"]) - got[p]).max()
    assert d < TOL_PX, (p, d)
print("SHARDED_A_OK")
"""


def test_sharded_function_a_matches_per_pair(subproc):
    out = subproc(SHARDED_A, devices=4, timeout=300)
    assert "SHARDED_A_OK" in out


STEAL = PRELUDE + r"""
import time
from repro.core import registration

# Device 1's worker is slow: its neighbours' workers steal its elements.
slow = registration._ChipWorker.__call__
def slowed(self, a, b):
    if self.chip == 1:
        time.sleep(0.3)
    return slow(self, a, b)
registration._ChipWorker.__call__ = slowed
s, res, plans, traffic = run()
assert traffic["steals"] > 0, traffic
# A steal copies its element's frame to the thief's device.
assert traffic["moved_bytes"] >= traffic["steals"] * 64 * 64 * 4, traffic
diff = np.abs(np.asarray(res.deformations["shift"])
              - sequential_shift()).max()
assert diff < TOL_PX, diff
print("STEAL_OK", traffic)
"""


def test_forced_cross_device_steal_moves_frames(subproc):
    out = subproc(STEAL, devices=4, timeout=300)
    assert "STEAL_OK" in out


ONE = PRELUDE + r"""
from repro.core.engine import dispatch
s, res, plans, traffic = run(devices=1)
# devices=1 on a host with four: every frame on one device, nothing placed.
assert s._chips is None and traffic["placed_bytes"] == 0
assert all(p[0] != "hierarchical" for p in plans), plans
# One device holding the frames (op_concurrency 1 on a TPU) plans the chain.
d = dispatch(8, domain="element", op_cost=0.1, workers=13, op_concurrency=1)
assert (d.backend, d.algorithm) == ("element", "sequential")
print("ONE_OK")
"""


def test_one_device_session_stays_on_one_device(subproc):
    out = subproc(ONE, devices=4, timeout=300)
    assert "ONE_OK" in out


SPANS = PRELUDE + "BENCH = %r\n" % os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"
) + r"""
import sys, tempfile, time
sys.path.insert(0, BENCH)
import program_trace
from repro.core import registration

slow = registration._ChipWorker.__call__
def slowed(self, a, b):
    if self.chip == 1:
        time.sleep(0.3)
    return slow(self, a, b)
registration._ChipWorker.__call__ = slowed
logdir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
with jax.profiler.trace(logdir, profiler_options=opts):
    s, res, plans, traffic = run()
ev = program_trace.load(logdir)
named = lambda n: [st for name, _, _, st in ev if name == n]
shards = named("repro.fn_a.shard")
assert sorted(st["chip"] for st in shards) == sorted(list(range(4)) * 3)
assert sum(st["pairs"] for st in shards) == 23
place = named("repro.frames.place")
assert len(place) == 3
assert sum(st["bytes"] for st in place) == traffic["placed_bytes"]
assert {st["chip"] for st in named("repro.fn_b")} == {0, 1, 2, 3}
assert {st["chips"] for st in named("repro.scan.plan")} == {4}
steals = named("repro.scan.steal")
assert len(steals) == traffic["steals"] > 0, (len(steals), traffic)
for st in steals:
    assert abs(st["from_chip"] - st["to_chip"]) == 1 and st["elements"] == 1
assert sum(st["bytes"] for st in steals) <= traffic["moved_bytes"]
assert all(st["session"] == s.id for st in shards + place + steals)
print("SPANS_OK", len(steals))
"""


def test_spans_across_devices(subproc):
    """``repro.fn_a.shard`` per device per feed, ``repro.frames.place``
    with the bytes ``traffic()`` counts, ``chip`` on ``repro.fn_b``,
    ``chips`` on ``repro.scan.plan``, and one ``repro.scan.steal`` per
    cross-device steal."""
    out = subproc(SPANS, devices=4, timeout=300)
    assert "SPANS_OK" in out


UNEVEN = PRELUDE + r"""
# Chunks that do not split evenly over four devices (10 = 3+3+3+1 with
# two padding frames, 7 = 2+2+2+1, then a lone frame) and chunks that
# arrive on one device: the session lays them out itself.
with repro.open_series(repro.RegisterSeriesConfig(devices=4)) as s:
    for lo, hi in ((0, 10), (10, 17), (17, 23), (23, 24)):
        s.feed(frames[lo:hi])
    res = s.result()
    traffic = s.traffic()
got = np.asarray(res.deformations["shift"])
assert got.shape == (24, 2), got.shape
diff = np.abs(got - sequential_shift()).max()
assert diff < TOL_PX, diff
assert traffic["placed_bytes"] > 0, traffic
print("UNEVEN_OK", diff)
"""


def test_uneven_chunks_match_sequential(subproc):
    out = subproc(UNEVEN, devices=4, timeout=300)
    assert "UNEVEN_OK" in out
