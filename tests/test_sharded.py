"""Sharded multi-device execution: exscan plans, boundary ledger, dispatch,
and 8-virtual-device subprocess runs (bit-exact vs the single-device engine).
"""

import math

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# exscan circuit + collective lowering (fast, single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [1, 2, 3, 5, 8, 16])
def test_exscan_circuit_oracle(p):
    """Element-level simulation of the 2p-wire circuit: wire i ends with the
    exclusive prefix x_0 .. x_{i-1} in exactly ceil(log2 p) rounds."""
    from repro.core.circuits import exscan_num_rounds, get_exscan_circuit

    circ = get_exscan_circuit(p)
    circ.validate()
    assert len(circ.rounds) == exscan_num_rounds(p)
    assert circ.exclusive
    # op = tuple concatenation (free monoid: associative, non-commutative,
    # and the result spells out exactly which inputs combined in what order)
    wires = [() for _ in range(p)] + [(i,) for i in range(p)]
    for rnd in circ.rounds:
        snap = list(wires)
        for kind, src, dst in rnd:
            assert kind == "c"
            wires[dst] = snap[src] + snap[dst]
    for i in range(p):
        assert wires[i] == tuple(range(i)), (p, i, wires[i])


@pytest.mark.parametrize("p", [2, 3, 5, 8])
def test_exscan_collective_lowering(p):
    """registers=2 lowering: every round sends the s register, one-to-one."""
    from repro.core.distributed import exscan_plan
    from repro.core.engine.backends import lower_collective

    rounds = lower_collective(exscan_plan(p), registers=2)
    assert len(rounds) == math.ceil(math.log2(p))
    for rnd in rounds:
        assert rnd.send_reg == 1  # the window-sum register is what moves
        assert rnd.fanout == 1    # one-to-one ppermute, no multicast
        assert rnd.dst_mask.shape == (2, p)
        assert rnd.move_mask.shape == (2, p)


def test_exscan_plan_round0_moves():
    """The identity-initialised e register makes round 0's e-updates compile
    to moves — received-value overwrites, zero operator applications."""
    from repro.core.distributed import exscan_plan

    plan = exscan_plan(8)
    r0 = plan.rounds[0]
    e_moves = [m for m in r0.moves if m[1] < 8]
    assert len(e_moves) == 7  # every rank but 0 overwrites e with s_{i-1}
    assert all(out < 8 and src >= 8 for src, out, _f in e_moves)


def test_axis_size_guard():
    """_axis_size: an explicit size wins; otherwise the mesh axis's static
    size, read with jax.lax.axis_size inside shard_map."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.distributed import _axis_size

    assert _axis_size("x", 8) == 8
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    seen = []

    def body(v):
        seen.append(_axis_size("x", None))
        return v

    jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"))(
        jnp.zeros(4)
    )
    assert seen == [1]


# ---------------------------------------------------------------------------
# dispatcher rules (fast)
# ---------------------------------------------------------------------------


def test_dispatch_sharded_rules():
    from repro.core.engine import dispatch
    from repro.core.engine.cost import SHARDED_MIN_DEVICES, SHARDED_MIN_N

    d = dispatch(4096, domain="array", op_cost=1e-5,
                 devices=SHARDED_MIN_DEVICES)
    assert d.backend == "sharded" and d.algorithm == "exscan"
    assert d.devices == SHARDED_MIN_DEVICES
    d = dispatch(4096, domain="element", op_cost=1e-5, op_batchable=True,
                 devices=8)
    assert d.backend == "sharded"
    # every missing precondition keeps the existing single-device choice
    assert dispatch(4096, domain="array", op_cost=1e-5).backend != "sharded"
    assert dispatch(4096, domain="array", op_cost=1e-5,
                    devices=SHARDED_MIN_DEVICES - 1).backend != "sharded"
    assert dispatch(SHARDED_MIN_N - 1, domain="array", op_cost=1e-5,
                    devices=8).backend != "sharded"
    assert dispatch(4096, domain="element", op_cost=1e-5, op_batchable=None,
                    devices=8).backend != "sharded"
    assert dispatch(4096, domain="element", op_cost=1e-2, op_batchable=True,
                    devices=8).backend != "sharded"  # expensive op: threads


# ---------------------------------------------------------------------------
# shard geometry + boundary ledger (fast, host-only protocol logic)
# ---------------------------------------------------------------------------


def test_shard_geometry():
    from repro.core.engine.sharded import _shard_geometry

    n_pad, k, halo, blocks = _shard_geometry(4096, 8)
    assert n_pad == 4096 and k == 512
    assert blocks % 2 == 0 and halo == (blocks // 2) * (k // (2 * blocks))
    assert halo <= k // 4
    # padding: n not divisible by devices
    n_pad, k, _h, _b = _shard_geometry(1000, 8)
    assert n_pad == k * 8 and n_pad >= 1000
    # degenerate tiny shards: no halo, no stealing
    _np, _k, halo, _b = _shard_geometry(32, 8)
    assert halo == 0


def test_boundary_ledger_claims_and_finalize():
    from repro.core.engine.sharded import BoundaryLedger, DEFAULT_GAP_BLOCKS

    b = DEFAULT_GAP_BLOCKS
    led = BoundaryLedger(num_gaps=7, blocks=b)
    # Shard 3 drains both its gaps before its neighbours even arrive.
    drained = 0
    while led.attempt(3):
        drained += 1
    assert drained == 2 * b  # both adjacent gaps fully claimed
    kl, kr = led.claims(3)
    assert kl + kr >= 0 and 0 <= kl <= b and 0 <= kr <= b
    # Virtual edge gaps always report the static border.
    kl0, _kr0 = led.claims(0)
    assert kl0 == b // 2
    _kl7, kr7 = led.claims(7)
    assert kr7 == b // 2
    # Finalize is idempotent and conserves blocks: every interior gap's
    # left + right claims cover it exactly.
    for s in range(8):
        led.claims(s)
    for g in led.gaps:
        assert g.taken_left + g.taken_right == b
    # Remainder of an untouched gap went left, deterministically: shard 0's
    # right gap finalizes fully to its left side (kr = all b blocks; kl is
    # the virtual-edge static border).
    untouched = BoundaryLedger(num_gaps=1, blocks=b)
    kl, kr = untouched.claims(0)
    assert (kl, kr) == (b // 2, b)
    assert untouched.forced == b


def test_boundary_ledger_steal_direction_prefers_straggler():
    from repro.core.engine.sharded import BoundaryLedger

    led = BoundaryLedger(num_gaps=2, blocks=4)
    # Shards 0 and 2 arrive; shard 1 never does (the straggler).  Both
    # neighbours must claim *toward* it (gap 0 right side, gap 1 left side).
    for _ in range(8):
        led.attempt(0)
    for _ in range(8):
        led.attempt(2)
    assert led.gaps[0].taken_left == 4   # shard 0 drained gap 0 leftward...
    assert led.gaps[1].taken_right == 4  # ...and shard 2 drained gap 1
    assert led.cross_steals >= 4         # claims crossed the static border


def test_boundary_ledger_sanitizer_anchoring_and_mutation():
    """Race-aware tooling covers the new boundary-gap callback path.

    Anchoring: concurrent drains of a real :class:`BoundaryLedger` hit the
    kinded ``shard.gap.*`` sync points and produce *zero* race reports —
    every ledger access is ordered by ``shard.ledger.lock``.  Mutation: a
    ledger variant whose claim-count update drops the lock (exactly the
    discipline the real ``attempt`` follows) must be flagged by the
    happens-before sanitizer — otherwise the sanitizer could not have
    caught the bug being reintroduced.
    """
    import threading

    from repro.analysis.sync import (
        get_race_tracker,
        observed_labels,
        reset_observed,
        reset_race_tracker,
        set_checking,
        sync_point,
    )
    from repro.core.engine.sharded import BoundaryLedger

    set_checking(True)
    reset_observed()
    reset_race_tracker()
    try:
        led = BoundaryLedger(num_gaps=3, blocks=4)

        def drain(shard):
            while led.attempt(shard):
                pass
            led.claims(shard)  # finalizes adjacent gaps

        threads = [threading.Thread(target=drain, args=(s,)) for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for g in led.gaps:
            assert g.taken_left + g.taken_right == 4
        seen = observed_labels()
        for label in ("shard.gap.seat", "shard.gap.claim",
                      "shard.gap.finalize"):
            assert label in seen, (label, seen)
        assert not [r for r in get_race_tracker().races()
                    if r.var == "shard.ledger"]

        class _UnlockedClaimLedger(BoundaryLedger):
            # MUTATION: the cross-steal counter update no longer holds (or
            # declares) the ledger lock.
            def attempt(self, shard):  # noqa: ARG002 — twin keeps the API
                sync_point("shard.gap.claim", "write", var="shard.ledger")
                self.cross_steals += 1
                return 0

        bad = _UnlockedClaimLedger(num_gaps=1, blocks=4)
        threads = [threading.Thread(target=bad.attempt, args=(s,))
                   for s in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        races = [r for r in get_race_tracker().races()
                 if r.var == "shard.ledger"]
        assert races, "sanitizer missed the unlocked ledger mutation"
    finally:
        # Deliberate seeded race: don't leak the report into the conftest
        # sessionfinish gate.
        reset_race_tracker()
        reset_observed()
        set_checking(False)


# ---------------------------------------------------------------------------
# simulator: exscan schedule (fast)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [2, 4, 8])
def test_simulator_exscan_rounds(p):
    from repro.core.simulator import exponential_costs, simulate_distributed_scan

    costs = exponential_costs(1024)
    r_ex = simulate_distributed_scan(costs, ranks=p, algorithm="exscan")
    r_in = simulate_distributed_scan(costs, ranks=p, algorithm="ladner_fischer")
    assert r_ex.phase2_rounds == math.ceil(math.log2(p))
    # Round-efficiency: the exscan schedule beats inclusive + shift.
    assert r_ex.phase2_rounds < r_in.phase2_rounds
    # Same phase-1 work, same costs: the correctness of phases is unchanged.
    assert r_ex.phase1_end == r_in.phase1_end


# ---------------------------------------------------------------------------
# 8-virtual-device subprocess runs
# ---------------------------------------------------------------------------

SHARDED_SNIPPET = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.engine import scan, sharded
from repro.core import distributed as dist
from repro.core.simulator import simulate_distributed_scan, constant_costs

assert jax.device_count() == 8
rng = np.random.default_rng(7)

# --- auto-dispatch, bit-exact vs the single-device vector oracle
xs = jnp.asarray(rng.integers(0, 100, 4096).astype(np.float32))
ys = scan(jnp.add, xs, op_cost=1e-5)
st = sharded.last_stats
assert st is not None and st.devices == 8, "dispatcher did not go sharded"
oracle = scan(jnp.add, xs, backend="vector")
assert np.array_equal(np.asarray(ys), np.asarray(oracle))

# --- executed phase-2 schedule == lowering == simulator prediction
assert st.phase2_algorithm == "exscan"
assert st.phase2_rounds == 3                      # ceil(log2 8)
assert dist.last_exscan_rounds() == st.phase2_rounds
sim = simulate_distributed_scan(constant_costs(4096), ranks=8,
                                algorithm="exscan")
assert sim.phase2_rounds == st.phase2_rounds
print("ROUNDS_OK", st.phase2_rounds)

# --- seeded
ys = scan(jnp.add, xs, backend="sharded", seed=jnp.float32(1000.0))
assert np.array_equal(np.asarray(ys), np.asarray(oracle) + 1000.0)

# --- masked (where): False elements are the identity
where = (rng.random(4096) < 0.7).tolist()
ys = scan(jnp.add, xs, backend="sharded", where=where)
oracle_m = scan(jnp.add, xs, backend="vector", where=where)
assert np.array_equal(np.asarray(ys), np.asarray(oracle_m))

# --- pytree (non-commutative affine compose), exactly-associative ints
m = jnp.asarray(np.where(rng.random(4096) < 0.004, 2.0, 1.0).astype(np.float32))
c = jnp.asarray(rng.integers(-4, 5, 4096).astype(np.float32))
aff = lambda a, b: (a[0] * b[0], a[1] * b[0] + b[1])
ym, yc = scan(aff, (m, c), backend="sharded")
om, oc = scan(aff, (m, c), backend="vector")
assert np.array_equal(np.asarray(ym), np.asarray(om))
assert np.array_equal(np.asarray(yc), np.asarray(oc))

# --- stealing off: same bits, no ledger traffic
ys = scan(jnp.add, xs, backend="sharded", stealing=False)
assert np.array_equal(np.asarray(ys), np.asarray(oracle))
assert sharded.last_stats.boundary_claims == []

# --- element domain: batchable op over a python list
items = [np.float32(v) for v in rng.integers(0, 50, 2048)]
def addel(a, b):
    return a + b
addel.op_batchable = True
addel.op_identity = np.float32(0.0)
ys = scan(addel, items, op_cost=1e-5)
assert sharded.last_stats is not None
assert np.array_equal(np.asarray(ys, dtype=np.float32),
                      np.cumsum(np.asarray(items, dtype=np.float32)))

# --- a series session on 8 devices pins a mesh for the sharded path
from repro.service import SeriesSession, RegisterSeriesConfig
s = SeriesSession(RegisterSeriesConfig())
assert s._devices == 8 and s._mesh is not None
s.close()
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_8dev(subproc):
    out = subproc(SHARDED_SNIPPET, devices=8)
    assert "SHARDED_OK" in out
    assert "ROUNDS_OK 3" in out


SHARDED_4DEV_SNIPPET = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.engine import scan, sharded

assert jax.device_count() == 4
xs = jnp.asarray(np.random.default_rng(3).integers(0, 9, 1031).astype(np.float32))
ys = scan(jnp.add, xs, op_cost=1e-5)     # odd n: identity-flag tail padding
st = sharded.last_stats
assert st is not None and st.devices == 4 and st.phase2_rounds == 2
assert np.array_equal(np.asarray(ys), np.asarray(scan(jnp.add, xs,
                                                      backend="vector")))
print("SHARDED4_OK")
"""


@pytest.mark.slow
def test_sharded_4dev_padding(subproc):
    out = subproc(SHARDED_4DEV_SNIPPET, devices=4)
    assert "SHARDED4_OK" in out
