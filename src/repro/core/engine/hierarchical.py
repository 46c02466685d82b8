"""Hierarchical two-level reduce-then-scan backend (paper §4.2/§4.3).

The paper's headline configuration: N elements are split across S node-local
*segments*; each segment is reduced independently with the work-stealing
executor (Algorithm 1 — threads steal boundary elements from slower
neighbours), a *small* cross-segment scan runs over the S segment totals
through an existing flat backend (plan-driven, width S), and a final
local-apply pass folds each segment's exclusive prefix back into its
elements.  Work stays ~3N while the critical path collapses to
O(N/(S·T) + log S).

Two domains, same phase structure:

* **element** (Python list, expensive opaque operator — the registration
  operator): phase 1 runs ``work_stealing.stealing_reduce`` per segment, all
  segments concurrently; phase 3 runs seeded sequential applies, one pool
  task per stolen interval.  Both phases execute on the injected
  :mod:`repro.runtime.scheduler` pool (shared process-wide pool by
  default) — no threads are spawned here.  This is the host-level twin of
  the paper's MPI-nodes × OpenMP-threads deployment.
* **array** (pytree of arrays, vectorizable operator): phase 1/3 are
  vectorized segment scans/applies (``vmap`` + broadcast combine), routed
  through the fused Pallas tile kernels (``kernels/tile_scan.py``) on a TPU
  when every leaf is a float (leaves are packed into one array).

``last_stats`` (a :class:`HierStats`) records per-phase wall time, segment
boundaries and per-segment steal statistics for the most recent element
execution — consumed by ``benchmarks/bench_registration_e2e.py`` and the
pipeline's stage report.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.runtime.scheduler import get_default_pool
from repro.runtime.spans import span

from .backends import exec_element, exec_vector, register_backend
from .plan import ExecutionPlan, get_plan

Op = Callable[[Any, Any], Any]


@dataclasses.dataclass
class HierStats:
    """Telemetry of one hierarchical element-domain execution."""

    num_segments: int
    threads_per_segment: int
    segment_bounds: List[Tuple[int, int]]       # inclusive [lo, hi] per segment
    intervals: List[Tuple[int, int]]            # final per-thread intervals
    steal_stats: List[Any]                      # per-segment StealStats | None
    phase_seconds: Dict[str, float]
    total_ops: int
    cross_steal: bool = False                   # inter-segment stealing ran
    inter_segment_steals: List[int] = dataclasses.field(default_factory=list)
    rebalanced: bool = False                    # AOT cost-history segment sizing
    device_phase1: bool = False                 # batched vmap reduce, no threads
    phase2_rounds: int = 0                      # cross-segment comm rounds: the
    # inclusive plan's rounds + 1 for the exclusive shift a distributed
    # lowering would pay (compare with the sharded backend's exscan count)

    def imbalance(self) -> float:
        """Max relative busy-time imbalance across segments (paper Fig. 5b)."""
        vals = [s.imbalance() for s in self.steal_stats if s is not None]
        return max(vals) if vals else 0.0

    def total_inter_segment_steals(self) -> int:
        """Boundary elements claimed across segment borders (phase 1)."""
        return sum(self.inter_segment_steals)


#: Stats of the most recent element-domain hierarchical execution.
last_stats: Optional[HierStats] = None


def segment_bounds(n: int, s: int) -> List[Tuple[int, int]]:
    """Contiguous near-even split of [0, n) into s inclusive intervals."""
    base, extra = divmod(n, s)
    out = []
    lo = 0
    for i in range(s):
        hi = lo + base + (1 if i < extra else 0) - 1
        out.append((lo, hi))
        lo = hi + 1
    return out


# ---------------------------------------------------------------------------
# element domain, device phase 1 — batched vmap reduce instead of threads
# ---------------------------------------------------------------------------


def _exec_hier_device(
    op: Op,
    xs: Sequence[Any],
    stacked,
    *,
    num_segments: int,
    seed: Any,
    interpret: Optional[bool],
    use_pallas: Optional[bool],
) -> Tuple[list, Any]:
    """Device-resident phase 1 for batchable operators.

    The element list is stacked to the array domain, the whole two-level
    reduce-then-scan runs as vectorized device launches
    (:func:`_exec_hier_array`), an optional seed folds in with **one**
    batched operator application, and the result is unstacked back to a
    list.  No WorkerPool tasks: for a cheap batchable operator the
    per-task Python dispatch is the phase-1 critical path, not the
    operator.
    """
    import jax
    import jax.numpy as jnp

    from .cost import _largest_divisor_at_most

    global last_stats
    n = len(xs)
    phase: Dict[str, float] = {}

    t0 = time.perf_counter()
    # Stacking happened in the caller (it doubles as the eligibility
    # check); the array path needs S | N.
    s = _largest_divisor_at_most(n, max(1, num_segments))
    plan = get_plan("ladner_fischer", s) if s > 1 else None
    phase["stack"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    ys_arr, _total = _exec_hier_array(
        op, plan, stacked, num_segments=s, interpret=interpret,
        use_pallas=use_pallas,
    )
    if seed is not None:
        seed_b = jax.tree.map(
            lambda sl, yl: jnp.broadcast_to(
                jnp.asarray(sl)[None], yl.shape
            ),
            seed, ys_arr,
        )
        ys_arr = op(seed_b, ys_arr)
    jax.block_until_ready(ys_arr)
    phase["device"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = [jax.tree.map(lambda t, i=i: t[i], ys_arr) for i in range(n)]
    total = jax.tree.map(lambda t: t[-1], ys_arr)
    phase["unstack"] = time.perf_counter() - t0

    last_stats = HierStats(
        num_segments=s,
        threads_per_segment=0,
        segment_bounds=segment_bounds(n, s),
        intervals=[],
        steal_stats=[None] * s,
        phase_seconds=phase,
        total_ops=0,  # device-side applications are not individually timed
        device_phase1=True,
        phase2_rounds=(plan.num_rounds() + 1) if plan is not None else 0,
    )
    return out, total


# ---------------------------------------------------------------------------
# element domain — segments reduced by the work-stealing executor
# ---------------------------------------------------------------------------


def _exec_hier_element(
    op: Op,
    plan: Optional[ExecutionPlan],
    xs: Sequence[Any],
    *,
    num_segments: int,
    num_threads: int,
    stealing: bool,
    seed: Any,
    cross_steal: Optional[bool] = None,
    element_costs: Optional[Sequence[float]] = None,
    pool=None,
) -> Tuple[list, Any]:
    from ..work_stealing import (
        _Gap,
        cross_start_positions,
        rebalance_boundaries,
        static_reduce,
        stealing_reduce,
    )
    from .telemetry import (
        OpTelemetry,
        element_costs_from,
        op_homes_from,
        op_worker_from,
    )

    global last_stats
    if pool is None:
        pool = get_default_pool()
    n = len(xs)
    t = max(1, num_threads)

    # Executor affinity: an operator that says which executor (chip) holds
    # each element's data gets one segment per run of one executor, in
    # place of ``num_segments``; that executor's worker reduces and
    # applies it, so only a cross-segment steal moves data.
    homes = op_homes_from(op, xs)
    rebalanced = False
    if homes is not None:
        bounds = _home_runs(homes)
        s = len(bounds)
        owners = [homes[lo] for lo, _ in bounds]
    else:
        s = max(1, min(num_segments, n))
        owners = list(range(s))
        # Ahead-of-time segment sizing: when the operator carries
        # per-element cost history (RegistrationOperator telemetry, or an
        # explicit ``element_costs``), size segments to equal *cost*
        # instead of equal count, so a known-expensive stretch starts with
        # fewer elements.
        costs = element_costs if element_costs is not None else (
            element_costs_from(op, n)
        )
        rebalanced = costs is not None and len(costs) == n and s > 1
        if rebalanced:
            bounds = rebalance_boundaries(list(costs), segment_bounds(n, s))
        else:
            bounds = segment_bounds(n, s)
    seg_workers = [op_worker_from(op, w) for w in owners]
    phase: Dict[str, float] = {}
    ops_count = 0

    # Cross-segment stealing (default on): finished segments drain shared
    # boundary gaps into still-running neighbours.  Needs stealing, >1
    # segment, and enough elements to seat every worker mid-range.
    cross = stealing and s > 1 if cross_steal is None else (
        cross_steal and stealing and s > 1
    )
    tcounts = [max(1, min(t, (hi - lo + 1) // 2)) for lo, hi in bounds]
    starts = cross_start_positions(bounds, tcounts, n) if cross else None
    cross = cross and starts is not None

    # --- phase 1: per-segment (stealing) reduction, segments concurrent.
    def reduce_segment(i: int):
        lo, hi = bounds[i]
        sop = seg_workers[i]
        seg = list(xs[lo : hi + 1])
        ln = hi - lo + 1
        t_eff = min(t, ln // 2)
        if t_eff >= 2:
            fn = stealing_reduce if stealing else static_reduce
            partials, st = fn(sop, seg, t_eff, pool=pool)
            intervals = [(lo + a, lo + b) for a, b in st.boundaries]
            reduce_ops = st.total_ops
        else:
            acc = seg[0]
            for item in seg[1:]:
                acc = sop(acc, item)
            partials, st, intervals = [acc], None, [(lo, hi)]
            reduce_ops = ln - 1
        # Inclusive scan over the thread partials (T is small) — its last
        # entry is the segment total for the global phase, its prefixes seed
        # the per-interval applies in phase 3.
        pscan = [partials[0]]
        for p in partials[1:]:
            pscan.append(sop(pscan[-1], p))
        return pscan, intervals, st, reduce_ops + len(pscan) - 1

    if cross:
        # Shared inter-segment gaps between the adjacent edge workers of
        # neighbouring segments, plus a per-segment rate EMA so direction
        # choice at a shared gap follows the *segment-level* Algorithm 1.
        offs = [0]
        for tc in tcounts:
            offs.append(offs[-1] + tc)
        inter: List[Optional[_Gap]] = [None] * (s + 1)
        for i in range(1, s):
            inter[i] = _Gap(starts[offs[i] - 1] + 1, starts[offs[i]],
                            border=bounds[i][0])
        seg_tel = [
            OpTelemetry(name=f"hier_seg{i}", ema_alpha=0.4) for i in range(s)
        ]

        def steal_counter(i: int):
            """A zero-length ``repro.scan.steal`` span at each element
            segment ``i`` claims beyond its border, with the bytes its
            worker's application copied between chips, tagged with the
            operator's session (the steal runs on a pool thread)."""
            def on_steal(_idx: int, side: str) -> None:
                src = owners[i - 1] if side == "L" else owners[i + 1]
                with span("scan.steal", getattr(op, "session", None),
                          from_chip=src, to_chip=owners[i],
                          elements=1,
                          bytes=getattr(seg_workers[i], "last_moved", 0)):
                    pass
            return on_steal

        def reduce_segment_cross(i: int):
            sop = seg_workers[i]
            partials, st = stealing_reduce(
                sop,
                xs,
                tcounts[i],
                starts=starts[offs[i] : offs[i + 1]],
                left_gap=inter[i],
                right_gap=inter[i + 1],
                outer_rates=(
                    seg_tel[i - 1].estimate if i > 0 else None,
                    seg_tel[i + 1].estimate if i < s - 1 else None,
                ),
                record=seg_tel[i].record,
                on_steal=steal_counter(i),
                pool=pool,
            )
            pscan = [partials[0]]
            for p in partials[1:]:
                pscan.append(sop(pscan[-1], p))
            return pscan, st.boundaries, st, st.total_ops + len(pscan) - 1

    t0 = time.perf_counter()
    with span("scan.phase1"):
        if cross:
            seg_results = pool.run_tasks(
                [functools.partial(reduce_segment_cross, i) for i in range(s)],
                label="hier_reduce_cross",
            )
            # Boundaries moved with the steals: report the segments' final
            # spans.
            bounds = [(r[1][0][0], r[1][-1][1]) for r in seg_results]
        elif s == 1:
            seg_results = [reduce_segment(0)]
        else:
            seg_results = pool.run_tasks(
                [functools.partial(reduce_segment, i) for i in range(s)],
                label="hier_reduce",
            )
    phase["reduce"] = time.perf_counter() - t0
    for _pscan, _intervals, _st, seg_ops in seg_results:
        ops_count += seg_ops

    # --- phase 2: small cross-segment scan over the S totals, giving each
    # segment its base (the exclusive prefix, seed included).
    t0 = time.perf_counter()
    totals = [r[0][-1] for r in seg_results]
    bases: List[Any] = [seed]
    if s > 1 and seed is not None:
        # Seeded: a chain from the seed, base_i = base_{i-1} . total_{i-1}:
        # S - 1 applications, where a circuit and then the seed combines
        # would take the circuit's work plus S - 1.  Every one starts from
        # the seed's first element (frame 0 of a series).
        with span("scan.phase2"):
            for tot in totals[:-1]:
                bases.append(op(bases[-1], tot))
        ops_count += s - 1
        rounds = s - 1
    elif s > 1:
        if plan is None or plan.n != s or plan.exclusive:
            plan = get_plan("ladner_fischer", s)
        with span("scan.phase2"):
            scanned, _ = exec_element(op, plan, totals)
        ops_count += plan.work()
        bases += scanned[:-1]
        rounds = plan.num_rounds() + 1
    else:
        rounds = 0
    phase["global"] = time.perf_counter() - t0

    # --- phase 3: seeded per-interval applies, all intervals concurrent.
    t0 = time.perf_counter()
    out: List[Any] = [None] * n
    jobs: List[Tuple[int, int, Any, int]] = []

    def apply_interval(job):
        lo, hi, acc, i = job
        sop = seg_workers[i]
        k = 0
        for idx in range(lo, hi + 1):
            acc = xs[idx] if acc is None else sop(acc, xs[idx])
            out[idx] = acc
            k += 1
        return k - (1 if job[2] is None else 0)

    with span("scan.phase3"):
        for i, (pscan, intervals, _st, _ops) in enumerate(seg_results):
            base = bases[i]
            for j, (lo, hi) in enumerate(intervals):
                if j == 0:
                    sj = base
                else:
                    sj = (pscan[j - 1] if base is None
                          else seg_workers[i](base, pscan[j - 1]))
                    ops_count += 0 if base is None else 1
                jobs.append((lo, hi, sj, i))
        if len(jobs) == 1:
            ops_count += apply_interval(jobs[0])
        else:
            ops_count += sum(
                pool.run_tasks(
                    [functools.partial(apply_interval, j) for j in jobs],
                    label="hier_apply",
                )
            )
    phase["apply"] = time.perf_counter() - t0

    last_stats = HierStats(
        num_segments=s,
        threads_per_segment=t,
        segment_bounds=bounds,
        intervals=[(lo, hi) for lo, hi, _, _ in jobs],
        steal_stats=[r[2] for r in seg_results],
        phase_seconds=phase,
        total_ops=ops_count,
        cross_steal=cross,
        inter_segment_steals=[
            r[2].cross_steals() if r[2] is not None else 0
            for r in seg_results
        ] if cross else [0] * s,
        rebalanced=rebalanced,
        phase2_rounds=rounds,
    )
    return out, out[-1]


def _home_runs(homes: Sequence[int]) -> List[Tuple[int, int]]:
    """Inclusive bounds of the runs of equal executor in ``homes``."""
    bounds, lo = [], 0
    for j in range(1, len(homes) + 1):
        if j == len(homes) or homes[j] != homes[lo]:
            bounds.append((lo, j - 1))
            lo = j
    return bounds


# ---------------------------------------------------------------------------
# array domain — vectorized segment scans + broadcast apply (Pallas-eligible)
# ---------------------------------------------------------------------------


def _pallas_eligible(xs) -> bool:
    import jax
    import jax.numpy as jnp

    return all(
        jnp.issubdtype(t.dtype, jnp.floating) for t in jax.tree.leaves(xs)
    )


def _exec_hier_tiles(op: Op, xs, s: int, *, interpret: Optional[bool]):
    """Phases 1 and 3 as the fused Pallas tile kernels.

    Leaves are packed column-wise into one (n, D) array (bit-exact, see
    ``_tiling.packed_op``).  A grid step holds one whole tile, so the
    ``s`` segments are cut into as many tiles (at least ``s``) as it takes
    for each tile's block to fit VMEM; the cross-tile phase is the vector
    executor over the tile totals.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels._tiling import (
        pack_leaves,
        packed_op,
        pad_rows,
        unpack_leaves,
        vmem_tiles,
    )
    from repro.kernels.tile_scan import tile_apply, tile_local_scan

    x2, spec = pack_leaves(xs)
    n, d = x2.shape
    pop = packed_op(op, spec)
    t = vmem_tiles(n, d * x2.dtype.itemsize, s)
    x2p, _ = pad_rows(x2, t)
    local, partials = tile_local_scan(pop, x2p, t, interpret=interpret)
    gscan, _ = exec_vector(pop, get_plan("ladner_fischer", t), partials[:, 0])
    seeds = jnp.concatenate([partials[:1, 0], gscan[:-1]], axis=0)
    y2 = tile_apply(pop, local, seeds[:, None], interpret=interpret)[:n]
    ys = unpack_leaves(y2, spec)
    return ys, jax.tree.map(lambda t: t[-1], ys)


def _exec_hier_array(
    op: Op,
    plan: Optional[ExecutionPlan],
    xs,
    *,
    num_segments: int,
    interpret: Optional[bool],
    use_pallas: Optional[bool],
) -> Tuple[Any, Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels._tiling import resolve_interpret

    from ..scan import _local_inclusive_scan

    n = jax.tree.leaves(xs)[0].shape[0]
    s = num_segments
    if n % s:
        raise ValueError(
            f"hierarchical array scan needs N divisible by num_segments, "
            f"got N={n}, S={s}"
        )
    if plan is None or plan.n != s or plan.exclusive:
        plan = get_plan("ladner_fischer", s) if s > 1 else None
    if s == 1:
        ys = _local_inclusive_scan(op, xs)
        return ys, jax.tree.map(lambda t: t[-1], ys)

    if use_pallas is None:
        use_pallas = not resolve_interpret(None)
    if use_pallas and _pallas_eligible(xs):
        return _exec_hier_tiles(op, xs, s, interpret=interpret)

    k = n // s
    segs = jax.tree.map(lambda t: t.reshape((s, k) + t.shape[1:]), xs)
    local = jax.vmap(lambda seg: _local_inclusive_scan(op, seg))(segs)
    partials = jax.tree.map(lambda t: t[:, -1], local)
    gscan, _ = exec_vector(op, plan, partials)
    # Apply: segment i>0 folds in the inclusive global prefix of segments <i.
    excl = jax.tree.map(lambda t: t[:-1], gscan)
    head = jax.tree.map(lambda t: t[:1], local)
    rest = jax.tree.map(lambda t: t[1:], local)
    upd = jax.vmap(
        lambda e, seg: op(
            jax.tree.map(
                lambda t: jnp.broadcast_to(t[None], (k,) + t.shape), e
            ),
            seg,
        )
    )(excl, rest)
    out = jax.tree.map(lambda h, u: jnp.concatenate([h, u], 0), head, upd)
    ys = jax.tree.map(lambda t: t.reshape((n,) + t.shape[2:]), out)
    return ys, jax.tree.map(lambda t: t[-1], gscan)


# ---------------------------------------------------------------------------
# backend entry point
# ---------------------------------------------------------------------------


def exec_hierarchical(
    op: Op,
    plan: Optional[ExecutionPlan],
    xs,
    *,
    num_segments: Optional[int] = None,
    num_threads: Optional[int] = None,
    stealing: bool = True,
    seed: Any = None,
    cross_steal: Optional[bool] = None,
    element_costs: Optional[Sequence[float]] = None,
    interpret: Optional[bool] = None,
    use_pallas: Optional[bool] = None,
    device_phase1: Optional[bool] = None,
    pool=None,
    **_,
) -> Tuple[Any, Any]:
    """Two-level reduce-then-scan; ``plan`` covers the cross-segment phase.

    ``num_segments`` defaults to the plan width; ``num_threads`` is the
    work-stealing thread count *per segment* (element domain only).
    ``cross_steal`` extends Algorithm 1 to the segment level (shared
    boundary gaps; default on where feasible); ``element_costs`` is an
    optional per-element cost prior for ahead-of-time segment sizing
    (otherwise read from the operator's telemetry, if it has any).
    ``device_phase1`` runs element-domain phase 1 as one batched device
    launch instead of pool threads (operators advertising ``op_batchable``;
    falls back to threads when the elements don't stack).  ``pool`` is the
    scheduler segment reduces and interval applies run on (element domain;
    the process-wide shared pool by default).
    """
    s = num_segments if num_segments is not None else (plan.n if plan else 1)
    if isinstance(xs, list):
        if device_phase1:
            from .decoupled_backend import stack_elements

            stacked = stack_elements(xs)
            if stacked is not None:
                return _exec_hier_device(
                    op, xs, stacked,
                    num_segments=s, seed=seed,
                    interpret=interpret, use_pallas=use_pallas,
                )
            # Elements don't stack (opaque payloads): threads still work.
        return _exec_hier_element(
            op,
            plan,
            xs,
            num_segments=s,
            num_threads=num_threads if num_threads is not None else 2,
            stealing=stealing,
            seed=seed,
            cross_steal=cross_steal,
            element_costs=element_costs,
            pool=pool,
        )
    if seed is not None:
        raise NotImplementedError(
            "seeded hierarchical scan is element-domain only"
        )
    return _exec_hier_array(
        op, plan, xs, num_segments=s, interpret=interpret,
        use_pallas=use_pallas,
    )


register_backend("hierarchical", exec_hierarchical)
