"""Multilevel rigid image registration (paper §2.3, Berkels et al. [6]).

Function **A**: register template to reference by minimizing 1 - NCC with a
multilevel (image pyramid) scheme and gradient descent whose iteration count
is *data-dependent* (``lax.while_loop`` with a convergence criterion) — the
source of the unpredictable operator cost that motivates the paper.

Function **B** (the scan operator, §2.3.2): given phi_{i,j} and phi_{j,k},
start from the composition phi_{j,k} o phi_{i,j} — guaranteed to be within
the attraction basin when consecutive shifts stay below half the lattice
period — and refine with A on the frame pair (f_i, f_k).

The scan element is ``RegElement = (deformation, i, k)``: 3 floats + 2 ints,
the paper's 20-byte payload.  Images are read from a shared array (standing
in for the parallel filesystem).
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.spans import span

from .deformation import (
    Deformation,
    compose,
    downsample2,
    identity_deformation,
    ncc_distance,
)


@dataclasses.dataclass(frozen=True)
class RegistrationConfig:
    # Pyramid depth is kept shallow: downsampling shrinks the lattice period
    # and with it the attraction basin (period/2, §2.3.2) — 2 levels preserves
    # the basin while still accelerating convergence.
    levels: int = 2              # pyramid depth
    max_iters: int = 300         # per level
    lr_shift: float = 1.0        # gradient step for translation (pixels)
    lr_angle: float = 5e-4       # gradient step for rotation (radians) on
                                 # frames up to 96 x 96; see _angle_step
    tol: float = 1e-7            # stop when |Delta D| < tol
    estimate_rotation: bool = True


#: Mean squared distance of a pixel from the centre of a 96 x 96 frame, the
#: size ``lr_angle`` was tuned on.
_ANGLE_REF_R2 = 1536.0


def _angle_step(cfg: RegistrationConfig, shape: Tuple[int, int]) -> float:
    """Rotation step for one pyramid level of ``shape``.

    A rotation moves a pixel in proportion to its distance from the centre,
    so the curvature of the distance in the angle grows with the mean
    squared distance r2.  A fixed step overshoots on larger frames: at
    960 x 928 the minimiser oscillates until ``max_iters``.  The step
    shrinks as 1 / r2 beyond the tuned size (a Gauss-Newton scaling).
    """
    if not cfg.estimate_rotation:
        return 0.0
    h, w = shape
    r2 = (h * h + w * w - 2) / 12.0
    return cfg.lr_angle * min(1.0, _ANGLE_REF_R2 / r2)


class RegResult(NamedTuple):
    deformation: Deformation
    distance: jax.Array          # final 1 - NCC
    iterations: jax.Array        # total gradient iterations (cost proxy)
    # Iterations per pyramid level, coarse to fine: shape (..., levels).
    level_iterations: Optional[jax.Array] = None


def _minimize_level(
    ref: jax.Array,
    tmpl: jax.Array,
    init: Deformation,
    cfg: RegistrationConfig,
) -> Tuple[Deformation, jax.Array, jax.Array]:
    """Gradient flow on one pyramid level with data-dependent stopping.

    A step is one forward pass (warp + NCC) and one backward pass: the
    loop carries the loss and its gradient at ``d``, and one
    ``value_and_grad`` at the new point gives both the stopping test's
    loss and the next step's gradient.

    The loop is *per-lane frozen*: under ``vmap`` a batched ``while_loop``
    keeps executing the body until every lane converges, and a lane that
    already met the tolerance must not step on — that would make a pair's
    result depend on which batch it was registered with (chunked streaming
    ingest would diverge from batch ingest) and make the per-lane
    iteration count read the cohort maximum instead of the lane's own
    cost.  ``act`` masks the update, the carried gradient included, so
    every lane follows exactly its solo trajectory regardless of cohort
    (JAX's batching rule for a batched predicate selects the carry per
    lane as well).
    """

    loss_and_grad = jax.value_and_grad(lambda d: ncc_distance(ref, tmpl, d))
    ang_step = _angle_step(cfg, ref.shape)

    def active_of(state):
        _, _, prev, cur, it = state
        return jnp.logical_and(it < cfg.max_iters, jnp.abs(prev - cur) > cfg.tol)

    def body(state):
        d, g, prev, cur, it = state
        act = active_of(state)
        d_new = {
            "angle": d["angle"] - ang_step * g["angle"],
            "shift": d["shift"] - cfg.lr_shift * g["shift"],
        }
        new, g_new = loss_and_grad(d_new)
        keep = lambda nv, ov: jnp.where(act, nv, ov)
        return (
            jax.tree.map(keep, d_new, d),
            jax.tree.map(keep, g_new, g),
            keep(cur, prev),
            keep(new, cur),
            it + act.astype(jnp.int32),
        )

    l0, g0 = loss_and_grad(init)
    state = (init, g0, l0 + 1.0, l0, jnp.zeros((), jnp.int32))
    d, _, _, final, iters = jax.lax.while_loop(active_of, body, state)
    return d, final, iters


def _pyramid(img: jax.Array, levels: int):
    pyr = [img]
    for _ in range(levels - 1):
        pyr.append(downsample2(pyr[-1]))
    return pyr[::-1]  # coarse -> fine


def register_pair(
    ref: jax.Array,
    tmpl: jax.Array,
    init: Optional[Deformation] = None,
    cfg: RegistrationConfig = RegistrationConfig(),
) -> RegResult:
    """Function A: estimate phi with f_tmpl o phi ~= f_ref (multilevel).

    ``init=None`` starts from the identity, passed in as an argument: a
    start known at trace time would fold into a second compiled program.
    """
    if init is None:
        init = identity_deformation()
    return _register_pair(ref, tmpl, init, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _register_pair(ref, tmpl, init, cfg) -> RegResult:
    refs = _pyramid(ref, cfg.levels)
    tmps = _pyramid(tmpl, cfg.levels)
    scale = 2.0 ** (cfg.levels - 1)
    d = {"angle": init["angle"], "shift": init["shift"] / scale}
    dist = jnp.zeros(())
    level_iters = []
    for lvl, (r, t) in enumerate(zip(refs, tmps)):
        d, dist, iters = _minimize_level(r, t, d, cfg)
        level_iters.append(iters)
        if lvl != len(refs) - 1:
            d = {"angle": d["angle"], "shift": d["shift"] * 2.0}
    level_iters = jnp.stack(level_iters)
    return RegResult(d, dist, jnp.sum(level_iters), level_iters)


def lane_work(level_iterations, shape: Tuple[int, int]) -> Tuple[int, int]:
    """Pixel-steps of a vmapped batch of pairs: ``(useful, issued)``.

    ``level_iterations`` is the batch's ``(lanes, levels)`` host array.  A
    vmapped ``while_loop`` runs its body on every lane until the slowest
    lane stops, and a step on level ``l`` costs in proportion to its pixels
    ``p_l``: useful = sum_l p_l * sum_lanes it, issued = sum_l p_l * lanes *
    max_lanes it.
    """
    its = np.asarray(level_iterations, np.int64)
    h, w = shape
    pixels = []
    for _ in range(its.shape[-1]):
        pixels.append(h * w)
        h, w = h // 2, w // 2
    pixels = np.asarray(pixels[::-1], np.int64)    # coarse -> fine
    lanes = its.shape[0]
    return (int(its.sum(axis=0) @ pixels),
            int(lanes * its.max(axis=0) @ pixels))


# ---------------------------------------------------------------------------
# Series registration as a prefix scan
# ---------------------------------------------------------------------------


class RegElement(NamedTuple):
    """Scan element phi_{i,k}: 'f_k o phi ~= f_i' plus the index pair."""

    deformation: Deformation
    i: int
    k: int


class SeriesRegistrar:
    """Owns the frame series and exposes the scan operator (.)_B.

    ``refine=True`` is the paper's operator B (compose + re-register, data-
    dependent cost); ``refine=False`` degrades to pure composition (exactly
    associative, cheap — useful as an oracle and for vectorized execution).
    """

    def __init__(
        self,
        frames: jax.Array,            # (N, H, W)
        cfg: RegistrationConfig = RegistrationConfig(),
        refine: bool = True,
    ):
        self.frames = frames
        self.cfg = cfg
        self.refine = refine
        self.op_calls = 0
        self.total_iters = 0

    # -- preprocessing: function A on consecutive pairs (massively parallel).
    def preprocess(self) -> list:
        n = self.frames.shape[0]
        elems = []
        for i in range(n - 1):
            res = register_pair(
                self.frames[i], self.frames[i + 1], None, self.cfg
            )
            self.total_iters += int(res.iterations)
            elems.append(RegElement(jax.device_get(res.deformation), i, i + 1))
        return elems

    def preprocess_vmapped(self) -> list:
        """Batched function-A over all consecutive pairs (one XLA launch)."""
        refs = self.frames[:-1]
        tmps = self.frames[1:]
        res = jax.vmap(lambda r, t: register_pair(r, t, None, self.cfg))(refs, tmps)
        n = self.frames.shape[0]
        return [
            RegElement(
                jax.tree.map(lambda a, i=i: a[i], res.deformation), i, i + 1
            )
            for i in range(n - 1)
        ]

    # -- the scan operator (.)_B  (paper §3).
    def op(self, a: RegElement, b: RegElement) -> RegElement:
        assert a.k == b.i, f"non-adjacent elements {a.i, a.k} . {b.i, b.k}"
        guess = compose(a.deformation, b.deformation)
        if not self.refine:
            return RegElement(guess, a.i, b.k)
        res = register_pair(
            self.frames[a.i], self.frames[b.k], guess, self.cfg
        )
        self.op_calls += 1
        self.total_iters += int(res.iterations)
        return RegElement(res.deformation, a.i, b.k)

    # -- plain sequential series registration (the paper's baseline).
    def sequential(self, elems=None) -> list:
        elems = self.preprocess() if elems is None else elems
        out = [elems[0]]
        for e in elems[1:]:
            out.append(self.op(out[-1], e))
        return out


# ---------------------------------------------------------------------------
# Engine adapter: Function B as a telemetered scan operator
# ---------------------------------------------------------------------------


def fused_ncc_distance(
    ref: jax.Array,
    tmpl: jax.Array,
    d: Deformation,
    *,
    tile: int = 32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """1 - NCC(ref, tmpl o d) through the fused warp+NCC Pallas kernel.

    One pass over output tiles computes the warp and the five NCC partial
    sums (``kernels/warp_ncc.py``) — the warped image never round-trips
    through HBM.  Equivalent to :func:`~repro.core.deformation.ncc_distance`
    up to fp accumulation order.
    """
    from repro.kernels._tiling import resolve_interpret
    from repro.kernels.warp_ncc import warp_ncc

    _, corr = warp_ncc(
        tmpl, ref, d["angle"], d["shift"], tile=tile,
        interpret=resolve_interpret(interpret),
    )
    return 1.0 - corr


def fused_ncc_eligible(shape: Tuple[int, int], tile: int = 32) -> bool:
    """The warp_ncc kernel tiles the output: both dims must divide by tile."""
    h, w = shape
    return h % tile == 0 and w % tile == 0


class RegistrationOperator:
    """Engine-facing adapter around Function B (the scan operator ``(.)_B``).

    Lets ``repro.core.engine.scan`` treat series registration as any other
    element-domain scan while closing two loops the raw method can't:

    * **cost telemetry** — every application's wall time is recorded into an
      :class:`~repro.core.engine.telemetry.OpTelemetry`; the adapter exposes
      ``op_cost_estimate`` so the dispatcher routes the *next* call from
      observed costs (data-dependent iteration counts drift over a series).
      That time is host time: an application returns before its device
      work ends, but waits for room where the device's queue is full.
      Each one is also a ``repro.fn_b`` span in the profiler's trace,
      tagged with ``session``.
    * **guess check** — when ``skip_tol`` is set, the composed initial
      guess phi_{j,k} o phi_{i,j} is scored first and refinement is skipped
      when it already registers within tolerance.  ``fused=True`` scores it
      through the fused warp+NCC Pallas kernel (``kernels/warp_ncc.py``,
      tile-divisible frames), interpreted: the kernel does not lower for the
      TPU (its flat gather is refused), so it is never on by default and
      asking for it on a TPU raises here rather than mid-scan.

    Thread-safe — the work-stealing executors apply it concurrently.
    """

    # Process-wide record of which (frame shape, config, code path)
    # signatures have already traced+compiled ``register_pair``.  The first
    # application under a fresh signature is wall-clock dominated by XLA
    # compilation; classifying it (``telemetry.record(..., compile=True)``)
    # keeps seconds of one-off compile time out of the cost EMA the
    # dispatcher plans the whole series around.  Class-level on purpose:
    # the jit cache is process-wide, so a second operator instance over the
    # same signature starts warm.
    _warm_signatures: set = set()
    _warm_lock = threading.Lock()

    @classmethod
    def _reset_compile_tracking(cls) -> None:
        """Forget warm signatures (tests that clear jax caches)."""
        with cls._warm_lock:
            cls._warm_signatures.clear()

    def __init__(
        self,
        registrar: SeriesRegistrar,
        *,
        name: str = "registration_B",
        telemetry=None,
        skip_tol: Optional[float] = None,
        fused: Optional[bool] = None,
        tile: int = 32,
        session: Optional[str] = None,
    ):
        from .engine.telemetry import OpTelemetry

        self.registrar = registrar
        self.session = session
        # A fresh channel per adapter by default, so per-run statistics stay
        # per-run; pass get_telemetry(name) explicitly to accumulate across
        # runs under one process-wide channel.
        self.telemetry = (
            telemetry if telemetry is not None else OpTelemetry(name=name)
        )
        self.skip_tol = skip_tol
        self.tile = tile
        h, w = registrar.frames.shape[1:]
        if fused and jax.default_backend() == "tpu":
            raise ValueError(
                "fused=True needs the warp_ncc Pallas kernel, which does not "
                "lower for the TPU: Mosaic refuses its flat jnp.take gather "
                "(only 2-D gathers are supported). Leave fused unset."
            )
        self.fused = bool(fused) and fused_ncc_eligible((h, w), tile)
        # Frames placed across chips (a store with ``on``/``home``): each
        # application runs on one of them, ordered by device id.
        frames = registrar.frames
        devs = frames.devices() if hasattr(frames, "on") else ()
        self._chips = (sorted(devs, key=lambda d: d.id) if len(devs) > 1
                       else None)
        self._count_lock = threading.Lock()
        self._elem_prior: Optional[list] = None
        self._elem_obs: dict = {}

    # -- the dispatcher feedback hook (read by engine.scan via telemetry).
    @property
    def op_cost_estimate(self) -> Optional[float]:
        return self.telemetry.estimate()

    @property
    def op_imbalance_estimate(self) -> Optional[float]:
        """Observed max/mean per-call cost ratio; None until at least two
        samples exist — a single one (e.g. the ``prime()`` seed) always
        reads 1.0 and would wrongly disable cross-segment stealing.  Read
        by the dispatcher (``engine/cost.py:CROSS_STEAL_MIN_IMBALANCE``)."""
        return self.telemetry.imbalance() if self.telemetry.calls >= 2 else None

    @property
    def op_concurrency(self) -> Optional[int]:
        """Applications that can run at the same time: the accelerators
        that hold the frames, each running one program at a time, or the
        devices the frames were placed across.  None for frames on one CPU
        device, whose client runs programs from several threads at once.
        Read by the dispatcher, which caps its worker budget there
        (``engine/cost.py:worker_budget``) and gives each device a worker
        when there are several."""
        devices = getattr(self.registrar.frames, "devices", None)
        if devices is None:
            return None
        devs = devices()
        return (len({d for d in devs if d.platform != "cpu"})
                or (len(devs) if len(devs) > 1 else None))

    def op_homes(self, elems) -> Optional[list]:
        """The chip (an index into the frames' devices) that holds each
        element's frame ``k``, or None when the frames are on one device.
        The hierarchical backend makes each run of one chip a segment."""
        if self._chips is None:
            return None
        frames = self.registrar.frames
        return [self._chips.index(frames.home(e.k)) for e in elems]

    def op_worker(self, chip: int) -> "_ChipWorker":
        """This operator as the worker of ``chip`` applies it: every
        application runs on that chip, and frames held elsewhere are
        copied there (a cross-chip steal moves its element's frame)."""
        if self._chips is None:
            return _ChipWorker(self, None, chip)
        return _ChipWorker(self, self._chips[chip % len(self._chips)], chip)

    def prime(self, seconds_per_call: float) -> None:
        """Seed the cost estimate before the first application (e.g. from
        the function-A preprocessing stage, whose per-pair cost is the same
        minimiser on the same frames)."""
        self.telemetry.record(seconds_per_call)

    def prime_elements(self, costs) -> None:
        """Seed *per-element* relative cost priors (any unit — e.g. the
        function-A per-pair iteration counts, the paper's cost proxy).
        Consumed by the hierarchical backend's ahead-of-time segment
        sizing: segments start equal-*cost*, not equal-count."""
        with self._count_lock:
            self._elem_prior = [float(c) for c in costs]

    def element_cost_estimates(self, n: int) -> Optional[list]:
        """Relative per-element cost vector combining the prior with
        observed per-application wall times, or None when neither exists
        at this length.  Units differ (iteration counts vs seconds), so
        observations are rescaled by aligning the two means *over the
        observed indices* — normalizing observations by their own subset
        mean instead would erase the imbalance signal (observing only the
        stragglers, the likeliest case since they run longest, would map
        every straggler to ~1.0)."""
        with self._count_lock:
            prior = self._elem_prior
            obs = dict(self._elem_obs)
        obs = {j: v for j, v in obs.items() if 0 <= j < n and v > 0}
        have_prior = prior is not None and len(prior) == n
        if have_prior:
            m = sum(prior) / n
            out = [p / m if m > 0 else 1.0 for p in prior]
        elif len(obs) == n:
            out = [1.0] * n  # full coverage: pure rescale below
        else:
            # No prior and only partial observations: there is no basis to
            # rank unobserved elements against observed ones, and rescaling
            # the observed subset against its own mean is exactly the
            # signal-erasing normalization documented above.  Decline to
            # resize rather than mislead.
            return None
        if obs:
            obs_mean = sum(obs.values()) / len(obs)
            prior_mean_at_obs = sum(out[j] for j in obs) / len(obs)
            scale = prior_mean_at_obs / obs_mean if obs_mean > 0 else 0.0
            if scale > 0:
                for j, v in obs.items():
                    out[j] = v * scale
        return out

    def _guess_distance(self, ref, tmpl, guess):
        if self.fused:
            return fused_ncc_distance(ref, tmpl, guess, tile=self.tile)
        return ncc_distance(ref, tmpl, guess)

    def __call__(self, a: RegElement, b: RegElement) -> RegElement:
        return self._apply(a, b, None)[0]

    def _frames_on(self, i: int, k: int, device):
        """Frames ``i`` and ``k`` on ``device`` (as stored when the frames
        are on one device), and the frame bytes copied there."""
        frames = self.registrar.frames
        if device is None:
            return frames[i], frames[k], 0
        ref, moved_ref = frames.on(i, device)
        tmpl, moved_tmpl = frames.on(k, device)
        return ref, tmpl, moved_ref + moved_tmpl

    def _apply(self, a: RegElement, b: RegElement, device):
        """One application of function B, and the frame bytes it copied
        between chips."""
        import time

        reg = self.registrar
        if self._chips is not None and device is None and reg.refine:
            # Outside a worker: the chip that holds frame k.
            device = reg.frames.home(b.k)
        chip = 0 if device is None else self._chips.index(device)
        with span("fn_b", self.session, i=a.i, k=b.k, chip=chip):
            t0 = time.perf_counter()
            sig = (
                tuple(reg.frames.shape[1:]), reg.cfg, reg.refine,
                self.skip_tol is not None, self.fused, device,
            )
            # Cold until the first call under this signature *completes*:
            # concurrent calls that start while the compile is in flight all
            # block on it and would otherwise poison the EMA with its wall time.
            with RegistrationOperator._warm_lock:
                cold = sig not in RegistrationOperator._warm_signatures
            # Attribute the cost to whichever operands ARE single scan
            # elements — left folds (stealing_reduce extending left) pass the
            # fresh element as ``a`` and the partial as ``b``, right folds the
            # reverse; indexing ``b`` unconditionally would credit half of
            # phase 1 to one unrelated right-edge element.  When both are
            # single (a thread's first combine) the registration involves both
            # frames, so both EMAs receive the sample.  Partial∘partial
            # combines (pscan, phase 2) have no single element and are skipped.
            elem_idxs = [e.k - 1 for e in (a, b) if e.k - e.i == 1]
            try:
                assert a.k == b.i, f"non-adjacent elements {a.i, a.k} . {b.i, b.k}"
                da, db = a.deformation, b.deformation
                if device is not None:
                    da, db = jax.device_put((da, db), device)
                guess = compose(da, db)
                if not reg.refine:
                    return RegElement(guess, a.i, b.k), 0
                ref, tmpl, moved = self._frames_on(a.i, b.k, device)
                if self.skip_tol is not None:
                    dist = self._guess_distance(ref, tmpl, guess)
                    if float(dist) < self.skip_tol:
                        return RegElement(guess, a.i, b.k), moved
                res = register_pair(ref, tmpl, guess, reg.cfg)
                return RegElement(res.deformation, a.i, b.k), moved
            finally:
                dt = time.perf_counter() - t0
                self.telemetry.record(dt, compile=cold)
                with RegistrationOperator._warm_lock:
                    RegistrationOperator._warm_signatures.add(sig)
                # A compile-dominated sample is no basis for per-element cost
                # ranking either — skip the observation, keep the prior.
                if elem_idxs and not cold:
                    with self._count_lock:
                        for j in elem_idxs:
                            prev = self._elem_obs.get(j)
                            self._elem_obs[j] = (
                                dt if prev is None else 0.5 * prev + 0.5 * dt
                            )


class _ChipWorker:
    """A :class:`RegistrationOperator` bound to one chip
    (``op_worker``).  ``last_moved`` holds the frame bytes its latest
    application copied between chips: the hierarchical backend reads it
    after a cross-segment steal."""

    def __init__(self, op: RegistrationOperator, device, chip: int):
        self.op = op
        self.device = device
        self.chip = chip
        self.last_moved = 0

    def __call__(self, a: RegElement, b: RegElement) -> RegElement:
        out, self.last_moved = self.op._apply(a, b, self.device)
        return out
