"""Image registration: deformations, function A/B, series scan (paper §2.3/§3)."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: deterministic fallback sampler
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.deformation import (
    compose,
    compose_batched,
    inverse,
    make_deformation,
    ncc,
    ncc_distance,
    warp,
)
from repro.core.registration import (
    RegistrationConfig,
    SeriesRegistrar,
    _angle_step,
    _pyramid,
    register_pair,
)
from repro.core.scan import prefix_scan
from repro.core.work_stealing import work_stealing_scan
from repro.data.images import lattice_image, make_series

CFG = RegistrationConfig()


@settings(max_examples=25, deadline=None)
@given(
    a1=st.floats(-0.3, 0.3), a2=st.floats(-0.3, 0.3), a3=st.floats(-0.3, 0.3),
    t1=st.floats(-5, 5), t2=st.floats(-5, 5), t3=st.floats(-5, 5),
)
def test_compose_associative(a1, a2, a3, t1, t2, t3):
    """The scan operator must be associative (paper §2.3.3)."""
    da = make_deformation(a1, [t1, t2])
    db = make_deformation(a2, [t2, t3])
    dc = make_deformation(a3, [t3, t1])
    lhs = compose(compose(da, db), dc)
    rhs = compose(da, compose(db, dc))
    np.testing.assert_allclose(lhs["angle"], rhs["angle"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lhs["shift"], rhs["shift"], rtol=1e-4, atol=1e-5)


def test_compose_noncommutative():
    da = make_deformation(0.5, [3.0, 0.0])
    db = make_deformation(-0.2, [0.0, 2.0])
    ab = compose(da, db)
    ba = compose(db, da)
    assert not np.allclose(np.asarray(ab["shift"]), np.asarray(ba["shift"]))


def test_inverse():
    d = make_deformation(0.3, [2.0, -1.5])
    i = compose(d, inverse(d))
    np.testing.assert_allclose(i["angle"], 0.0, atol=1e-6)
    np.testing.assert_allclose(i["shift"], 0.0, atol=1e-5)


def test_compose_batched_matches_compose():
    key = jax.random.PRNGKey(0)
    a = {"angle": jax.random.normal(key, (5,)) * 0.1,
         "shift": jax.random.normal(key, (5, 2))}
    b = {"angle": jax.random.normal(key, (5,)) * 0.1 + 0.05,
         "shift": jax.random.normal(key, (5, 2)) - 0.2}
    batched = compose_batched(a, b)
    for i in range(5):
        single = compose(jax.tree.map(lambda t, i=i: t[i], a),
                         jax.tree.map(lambda t, i=i: t[i], b))
        np.testing.assert_allclose(batched["angle"][i], single["angle"], rtol=1e-5)
        np.testing.assert_allclose(batched["shift"][i], single["shift"], rtol=1e-4,
                                   atol=1e-6)


def test_warp_translation():
    img = jnp.zeros((32, 32)).at[16, 16].set(1.0)
    w = warp(img, make_deformation(0.0, [3.0, -2.0]))
    peak = np.unravel_index(np.argmax(np.asarray(w)), (32, 32))
    assert peak == (13, 18)  # warp(x) = img(x + shift)


@pytest.mark.parametrize("size", [(96, 96), (37, 129)])
@pytest.mark.parametrize("ang,shift", [
    (0.0, (0.0, 0.0)),         # identity: every coordinate an integer
    (0.03, (2.7, -1.3)),       # a frame-to-frame step
    (-0.4, (-40.5, 33.1)),     # wide row band, part of the frame clipped
    (1.57, (2.0, 3.0)),        # quarter turn
    (0.05, (1e4, -1e4)),       # entirely outside the frame
])
def test_warp_kernel_matches_gathers(size, ang, shift):
    """The TPU path of warp (Pallas neighbour fetch, interpreted here) gives
    bit-identical values to the four gathers, and the same derivative up to
    the order of its sums."""
    img = lattice_image(size, key=jax.random.PRNGKey(5))
    img = img + 0.3 * jax.random.normal(jax.random.PRNGKey(6), size)
    d = make_deformation(ang, list(shift))
    k = jax.jit(lambda i, d: warp(i, d, kernel=True))(img, d)
    g = jax.jit(lambda i, d: warp(i, d, kernel=False))(img, d)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(g))

    def grad(kernel):
        return jax.jit(jax.grad(
            lambda d: (warp(img, d, kernel=kernel) * img).sum()))(d)

    gk, gg = grad(True), grad(False)
    scale = float(np.abs(np.asarray(gg["shift"])).max()) + 1.0
    np.testing.assert_allclose(np.asarray(gk["shift"]), np.asarray(gg["shift"]),
                               rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(float(gk["angle"]), float(gg["angle"]),
                               rtol=0, atol=1e-5 * scale * max(size))


def test_warp_kernel_under_vmap():
    """function A vmaps warp over a chunk's pairs: the kernel batches too."""
    imgs = jnp.stack([lattice_image(64, key=jax.random.PRNGKey(k))
                      for k in range(3)])
    ds = {"angle": jnp.array([0.0, 0.1, -0.05]),
          "shift": jnp.array([[1.5, -2.0], [0.3, 0.7], [-5.0, 4.0]])}
    k = jax.vmap(lambda i, d: warp(i, d, kernel=True))(imgs, ds)
    g = jax.vmap(lambda i, d: warp(i, d, kernel=False))(imgs, ds)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(g))


def test_ncc_properties():
    key = jax.random.PRNGKey(3)
    img = lattice_image(64, key=key)
    assert float(ncc(img, img)) > 0.999
    assert float(ncc(img, -img)) < -0.999
    noise = jax.random.normal(key, img.shape)
    assert abs(float(ncc(img, noise))) < 0.2


def test_register_pair_recovers_shift():
    frames, true = make_series(jax.random.PRNGKey(0), 4, size=96, noise=0.15)
    for i in range(3):
        res = register_pair(frames[i], frames[i + 1], None, CFG)
        rel = np.asarray(true["shift"][i + 1] - true["shift"][i])
        err = np.abs(np.asarray(res.deformation["shift"]) - rel).max()
        assert err < 0.25, (i, err)
        assert int(res.iterations) > 5  # actually iterated


def test_register_pair_converges_on_large_frames():
    """The rotation step shrinks with the frame: at 480 x 464 a fixed step
    overshoots, and the minimiser ran out its iterations 0.011 rad off."""
    frames, true = make_series(jax.random.PRNGKey(0), 2, size=(480, 464))
    res = register_pair(frames[0], frames[1], None, CFG)
    assert int(res.iterations) < CFG.levels * CFG.max_iters
    err = np.abs(np.asarray(res.deformation["shift"] - true["shift"][1])).max()
    assert err < 0.25, err
    assert abs(float(res.deformation["angle"] - true["angle"][1])) < 1e-3


def test_iteration_count_data_dependent():
    """The operator cost must vary with data (the paper's imbalance source)."""
    frames, _ = make_series(jax.random.PRNGKey(5), 10, size=96, noise=0.2)
    iters = [
        int(register_pair(frames[i], frames[i + 1], None, CFG).iterations)
        for i in range(9)
    ]
    assert len(set(iters)) > 3, iters


def test_series_scan_matches_sequential():
    """Prefix-scan registration == sequential registration (§2.3.3: both
    converge to equivalent minima; we check deformation agreement)."""
    frames, true = make_series(jax.random.PRNGKey(7), 10, size=96, noise=0.12)
    reg = SeriesRegistrar(frames)
    elems = reg.preprocess_vmapped()
    seq = reg.sequential(list(elems))

    reg2 = SeriesRegistrar(frames)
    out, stats = work_stealing_scan(reg2.op, list(elems), 3, stealing=True)
    for a, b in zip(seq, out):
        assert a.i == b.i and a.k == b.k
        np.testing.assert_allclose(
            np.asarray(a.deformation["shift"]),
            np.asarray(b.deformation["shift"]), atol=0.05,
        )
    # cumulative drift recovered
    est = np.stack([np.asarray(e.deformation["shift"]) for e in out])
    tru = np.asarray(true["shift"][1:])
    assert np.abs(est - tru).max() < 0.35


def test_pure_compose_scan_vectorized():
    """refine=False operator is exactly associative: every circuit agrees."""
    key = jax.random.PRNGKey(2)
    n = 16
    elems = {
        "angle": jax.random.normal(key, (n,)) * 0.05,
        "shift": jax.random.normal(key, (n, 2)) * 2.0,
    }
    ref = prefix_scan(compose_batched, elems, algorithm="sequential")
    for alg in ["dissemination", "ladner_fischer", "blelloch", "brent_kung"]:
        y = prefix_scan(compose_batched, elems, algorithm=alg)
        np.testing.assert_allclose(np.asarray(y["angle"]),
                                   np.asarray(ref["angle"]), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y["shift"]),
                                   np.asarray(ref["shift"]), rtol=1e-4, atol=1e-5)


def test_operator_imbalance_needs_two_samples():
    """A single telemetry sample (e.g. the pipeline's prime()) always reads
    max/mean == 1.0 and must NOT masquerade as observed balance — it would
    wrongly disable cross-segment stealing on the first scan."""
    from repro.core.registration import RegistrationOperator

    frames, _ = make_series(jax.random.PRNGKey(0), 3, size=32)
    op = RegistrationOperator(SeriesRegistrar(frames), name="t_imb")
    assert op.op_imbalance_estimate is None
    op.prime(0.5)
    assert op.op_imbalance_estimate is None  # one sample = no information
    op.telemetry.record(1.5)
    assert op.op_imbalance_estimate is not None


def test_element_cost_estimates_preserve_straggler_signal():
    """Observations are rescaled against the prior over the *observed
    indices*: seeing only the straggler must not renormalize it to ~1.0
    (subset-mean normalization erased exactly the signal AOT sizing
    needs)."""
    from repro.core.registration import RegistrationOperator

    frames, _ = make_series(jax.random.PRNGKey(0), 3, size=32)
    op = RegistrationOperator(SeriesRegistrar(frames), name="t_elem")
    assert op.element_cost_estimates(8) is None
    # No prior + partial observations = no basis to rank the unobserved:
    # must decline instead of renormalizing the subset to ~1.0.
    op._elem_obs[3] = 4.0
    assert op.element_cost_estimates(8) is None
    op._elem_obs.clear()
    op.prime_elements([8.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    base = op.element_cost_estimates(8)
    assert base[0] / base[1] == 8.0
    # One observation of the straggler only (it runs longest, so it is the
    # likeliest to be observed): relative costs must be preserved.
    op._elem_obs[0] = 4.0  # seconds
    est = op.element_cost_estimates(8)
    assert est[0] / est[1] > 6.0, est
    # Two observations shift the balance by their *relative* magnitudes.
    op._elem_obs[1] = 4.0  # element 1 measured as dear as the straggler
    est = op.element_cost_estimates(8)
    assert abs(est[0] - est[1]) < 1e-9
    assert est[0] > est[2]


# Above the float32 summation noise of the loss near a minimum: at the
# default 1e-7 two programs that round the loss differently may stop
# steps apart (a batched and a solo one, or a fused and a separate forward
# pass), so per-step agreement is checked where the stop follows the
# trajectory rather than the rounding.
TRAJ_CFG = dataclasses.replace(CFG, tol=1e-5)


def _two_pass_minimize_level(ref, tmpl, init, cfg):
    """Reference: the minimiser step as ``grad`` at ``d`` then a plain
    ``loss`` at ``d_new``, two forward passes and one backward a step."""
    loss = lambda d: ncc_distance(ref, tmpl, d)
    grad = jax.grad(loss)
    ang_step = _angle_step(cfg, ref.shape)

    def active_of(state):
        _, prev, cur, it = state
        return jnp.logical_and(it < cfg.max_iters, jnp.abs(prev - cur) > cfg.tol)

    def body(state):
        d, prev, cur, it = state
        act = active_of(state)
        g = grad(d)
        d_new = {
            "angle": d["angle"] - ang_step * g["angle"],
            "shift": d["shift"] - cfg.lr_shift * g["shift"],
        }
        new = loss(d_new)
        keep = lambda nv, ov: jnp.where(act, nv, ov)
        return (jax.tree.map(keep, d_new, d), keep(cur, prev), keep(new, cur),
                it + act.astype(jnp.int32))

    l0 = loss(init)
    state = (init, l0 + 1.0, l0, jnp.zeros((), jnp.int32))
    d, _, final, iters = jax.lax.while_loop(active_of, body, state)
    return d, final, iters


@partial(jax.jit, static_argnames=("cfg",))
def _two_pass_register_pair(ref, tmpl, cfg):
    """``register_pair`` from the identity with the two-pass step."""
    refs, tmps = _pyramid(ref, cfg.levels), _pyramid(tmpl, cfg.levels)
    d = {"angle": jnp.zeros(()), "shift": jnp.zeros((2,))}
    level_iters = []
    for lvl, (r, t) in enumerate(zip(refs, tmps)):
        d, _, iters = _two_pass_minimize_level(r, t, d, cfg)
        level_iters.append(iters)
        if lvl != len(refs) - 1:
            d = {"angle": d["angle"], "shift": d["shift"] * 2.0}
    return d, jnp.stack(level_iters)


@pytest.mark.parametrize("size", [(96, 96), (480, 464)])
def test_one_pass_step_follows_two_pass_trajectory(size):
    """Carrying value_and_grad through the loop changes the cost of a step,
    not the step: same deformation, same iterations per level."""
    frames, _ = make_series(jax.random.PRNGKey(11), 2, size=size, noise=0.15)
    res = register_pair(frames[0], frames[1], None, TRAJ_CFG)
    d_ref, its_ref = _two_pass_register_pair(frames[0], frames[1], TRAJ_CFG)
    np.testing.assert_allclose(np.asarray(res.deformation["shift"]),
                               np.asarray(d_ref["shift"]), atol=1e-4)
    np.testing.assert_allclose(float(res.deformation["angle"]),
                               float(d_ref["angle"]), atol=1e-6)
    its, its_ref = np.asarray(res.level_iterations), np.asarray(its_ref)
    assert np.abs(its - its_ref).max() <= 2, (its, its_ref)
    assert its.min() > 1


def test_vmapped_lanes_keep_their_solo_result():
    """The per-lane freeze: a lane that converges first stops stepping, so
    under vmap each pair keeps its own iterations and deformation."""
    frames, _ = make_series(jax.random.PRNGKey(5), 4, size=96, noise=0.2)
    refs, tmps = frames[:-1], frames[1:]
    batch = jax.vmap(lambda r, t: register_pair(r, t, None, TRAJ_CFG))(refs, tmps)
    solo = [register_pair(refs[i], tmps[i], None, TRAJ_CFG) for i in range(3)]
    solo_its = np.stack([np.asarray(s.level_iterations) for s in solo])
    # Pairs of different difficulty: a shared count would read the maximum.
    assert len({tuple(r) for r in solo_its}) == 3, solo_its
    np.testing.assert_array_equal(np.asarray(batch.level_iterations), solo_its)
    for i, s in enumerate(solo):
        np.testing.assert_allclose(np.asarray(batch.deformation["shift"][i]),
                                   np.asarray(s.deformation["shift"]), atol=1e-5)
        np.testing.assert_allclose(float(batch.deformation["angle"][i]),
                                   float(s.deformation["angle"]), atol=1e-7)
