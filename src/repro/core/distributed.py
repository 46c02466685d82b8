"""Distributed prefix scan over mesh axes (paper §4.1/§4.2) — shard_map/ppermute.

A precompiled :class:`~repro.core.engine.plan.ExecutionPlan` is executed
*across devices*: one scan element per device along a named mesh axis, one
plan round per communication round.  The per-round permutation tables, source
indices and destination masks are resolved once by
:func:`repro.core.engine.backends.lower_collective` (LRU-cached), not
re-derived from the circuit IR on every call.  One-to-one rounds lower to
``lax.ppermute`` (the MPI point-to-point sends of the paper); multicast
rounds — Ladner–Fischer's MPI_Bcast steps — lower to ``lax.all_gather`` + a
dynamic select, the TPU-idiomatic multicast (DESIGN.md §3).  This module is
the engine's ``collective`` backend.

Hierarchy: the paper replaces P flat ranks by P' ranks x T threads.  Here the
hierarchy is mesh axes — ``("pod", "data")``: an inner scan on the fast ICI
axis, a single outer scan on the slow inter-pod axis, exactly mirroring
"restrict the global phase to the highest hierarchy level" (§4.2/§4.3).

All functions are *collectives*: call them inside ``shard_map`` (or inside a
jit that is already manual-sharded).  ``axis_size`` must be the static size of
the named axis (JAX exposes it via ``lax.psum(1, axis)`` only dynamically, so
we take it as an argument; ``jax.lax.axis_size`` is used when available).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .circuits import get_exscan_circuit
from .engine.backends import lower_collective
from .engine.plan import ExecutionPlan, get_plan
from .scan import _local_inclusive_scan, _local_reduce, _tree_concat

Op = Callable[[Any, Any], Any]


def _axis_size(axis_name: str, axis_size: Optional[int]) -> int:
    if axis_size is not None:
        return int(axis_size)
    return int(jax.lax.axis_size(axis_name))  # static inside shard_map


def _where_tree(mask, a, b):
    return jax.tree.map(lambda x, y: jnp.where(mask, x, y), a, b)


def collective_scan_plan(op: Op, x, axis_name: str, plan: ExecutionPlan) -> Any:
    """Execute a precompiled plan's rounds as collectives across ``axis_name``.

    Every device runs every round's operator application and masks the result
    — the SPMD analogue of idle workers in the paper's Figure 2.
    """
    rounds = lower_collective(plan)  # raises for non-combine-only circuits
    my = lax.axis_index(axis_name)
    y = x
    for rnd in rounds:
        dst_mask = jnp.asarray(rnd.dst_mask)[my]
        if rnd.fanout == 1:
            recv = lax.ppermute(y, axis_name, perm=list(rnd.perm))
        else:
            # Multicast round (Ladner-Fischer broadcast): all_gather + select.
            gathered = lax.all_gather(y, axis_name, axis=0)
            src_idx = jnp.asarray(rnd.src_of)[my]
            recv = jax.tree.map(
                lambda t: lax.dynamic_index_in_dim(t, src_idx, 0, keepdims=False),
                gathered,
            )
        combined = op(recv, y)
        y = _where_tree(dst_mask, combined, y)
    return y


def collective_scan(
    op: Op,
    x,
    axis_name: str,
    *,
    algorithm: str = "ladner_fischer",
    axis_size: Optional[int] = None,
) -> Any:
    """Inclusive prefix scan of one element per device across ``axis_name``.

    Lowers the chosen circuit to a plan (cached across calls) and executes it
    with ppermute/all_gather rounds via :func:`collective_scan_plan`.
    """
    p = _axis_size(axis_name, axis_size)
    if p == 1:
        return x
    return collective_scan_plan(op, x, axis_name, get_plan(algorithm, p))


def exclusive_shift(x, axis_name: str, *, axis_size: Optional[int] = None):
    """Shift values one device to the right along the axis.  Device 0 receives
    zeros — callers must mask with ``lax.axis_index(axis) > 0``."""
    p = _axis_size(axis_name, axis_size)
    return lax.ppermute(x, axis_name, perm=[(i, i + 1) for i in range(p - 1)])


def exscan_plan(p: int) -> ExecutionPlan:
    """Plan for the Träff round-efficient exclusive scan over ``p`` ranks.

    The 2p-wire circuit's e-register starts as the identity, expressed to the
    planner via the wire mask — round 0's e-updates therefore compile into
    *moves* (received-value overwrites), not operator applications.
    """
    circ = get_exscan_circuit(p)
    return get_plan(circ, mask=[True] * p + [False] * p)


#: Trace-time log of executed exclusive-scan schedules: one entry per
#: ``exclusive_collective_scan`` lowering, the number of ppermute rounds.
#: Tests and benches assert the executed round count matches the Träff
#: schedule (ceil(log2 p)) and the simulator's prediction.
_exscan_rounds_log: List[int] = []


def last_exscan_rounds() -> Optional[int]:
    return _exscan_rounds_log[-1] if _exscan_rounds_log else None


def exclusive_collective_scan(
    op: Op,
    x,
    axis_name: str,
    *,
    axis_size: Optional[int] = None,
    init=None,
):
    """Round-efficient *exclusive* scan across ``axis_name`` (Träff 2025).

    Device i ends with x_0 (.) ... (.) x_{i-1} in ceil(log2 p) ppermute
    rounds — one round fewer than the naive inclusive-scan-then-shift
    (:func:`collective_scan` + :func:`exclusive_shift`): each round's single
    message carries the sender's window sum and updates *both* the exclusive
    prefix and the window registers of the receiver.

    Device 0 receives ``init`` (zeros by default) — callers must mask with
    ``lax.axis_index(axis) > 0`` unless ``init`` is a true identity of ``op``.
    """
    p = _axis_size(axis_name, axis_size)
    if init is None:
        init = jax.tree.map(jnp.zeros_like, x)
    if p == 1:
        return init
    rounds = lower_collective(exscan_plan(p), registers=2)
    _exscan_rounds_log.append(len(rounds))
    my = lax.axis_index(axis_name)
    regs = [init, x]  # [e, s]: exclusive prefix, window sum
    for rnd in rounds:
        # Exscan rounds are one-to-one by construction (fanout == 1).
        recv = lax.ppermute(regs[rnd.send_reg], axis_name, perm=list(rnd.perm))
        new_regs = []
        for r in range(2):
            cmask = jnp.asarray(rnd.dst_mask[r])[my]
            mmask = jnp.asarray(rnd.move_mask[r])[my]
            y = _where_tree(cmask, op(recv, regs[r]), regs[r])
            y = _where_tree(mmask, recv, y)
            new_regs.append(y)
        regs = new_regs
    return regs[0]


def _masked_total(y, axis_name: str, p: int):
    """Value held by the last device on the axis, broadcast to all devices.

    Implemented as a masked psum: one all-reduce, no gather of the full axis.
    """
    my = lax.axis_index(axis_name)
    is_last = my == p - 1
    masked = jax.tree.map(lambda t: jnp.where(is_last, t, jnp.zeros_like(t)), y)
    return lax.psum(masked, axis_name)


def hierarchical_collective_scan(
    op: Op,
    x,
    axis_names: Sequence[str],
    *,
    algorithms: Optional[Sequence[str]] = None,
    axis_sizes: Optional[Sequence[int]] = None,
) -> Any:
    """Inclusive scan across the flattened (outer..., inner) device hierarchy.

    ``axis_names`` ordered outer-to-inner, e.g. ("pod", "data"): the element
    order is pod-major.  Each level scans internally, then passes one summary
    per group up — the paper's hierarchical scan (§4.2) with mesh axes playing
    ranks/threads.  Only the outermost scan crosses the slow network.
    """
    if algorithms is None:
        # Non-innermost levels fold an *exclusive* group prefix — default to
        # the round-efficient exscan there; the innermost level is a plain
        # inclusive scan and keeps the paper's Ladner–Fischer circuit.
        algorithms = ["exscan"] * (len(axis_names) - 1) + ["ladner_fischer"]
    if axis_sizes is None:
        axis_sizes = [None] * len(axis_names)
    if len(axis_names) == 1:
        return collective_scan(
            op, x, axis_names[0], algorithm=algorithms[0], axis_size=axis_sizes[0]
        )
    inner_names = axis_names[1:]
    inner_algs = algorithms[1:]
    inner_sizes = axis_sizes[1:]
    # Scan within the inner hierarchy.
    y = hierarchical_collective_scan(
        op, x, inner_names, algorithms=inner_algs, axis_sizes=inner_sizes
    )
    # One summary per inner group = the last inner device's inclusive value.
    p_inner = [_axis_size(n, s) for n, s in zip(inner_names, inner_sizes)]
    total = y
    for n, p in zip(inner_names, p_inner):
        total = _masked_total(total, n, p)
    # Outer *exclusive* scan over group summaries, folded back into every
    # member of the group.  The default outer schedule is the round-efficient
    # exscan — ceil(log2 p) rounds instead of the legacy inclusive scan plus
    # shift (one round more, kept for explicitly-requested circuits).
    outer = axis_names[0]
    p_outer = _axis_size(outer, axis_sizes[0])
    if algorithms[0] in (None, "exscan"):
        g_prev = exclusive_collective_scan(op, total, outer, axis_size=p_outer)
    else:
        g = collective_scan(
            op, total, outer, algorithm=algorithms[0], axis_size=p_outer
        )
        g_prev = exclusive_shift(g, outer, axis_size=p_outer)
    has_prev = lax.axis_index(outer) > 0
    return _where_tree(has_prev, op(g_prev, y), y)


def exclusive_hierarchical_scan(
    op: Op,
    x,
    axis_names: Sequence[str],
    *,
    axis_sizes: Optional[Sequence[int]] = None,
) -> Any:
    """Exclusive scan across the flattened (outer..., inner) hierarchy.

    Every level runs the round-efficient exscan schedule directly — no
    inclusive scan followed by shifts (:func:`_exclusive_over_hierarchy`), so
    the slowest (outermost) axis sees exactly ceil(log2 p) rounds.  The
    hierarchically-first device receives zeros — callers must mask with
    :func:`_nonzero_linear_index`.
    """
    if axis_sizes is None:
        axis_sizes = [None] * len(axis_names)
    outer = axis_names[0]
    p_outer = _axis_size(outer, axis_sizes[0])
    if len(axis_names) == 1:
        return exclusive_collective_scan(op, x, outer, axis_size=p_outer)
    inner_names = axis_names[1:]
    inner_sizes = axis_sizes[1:]
    e_in = exclusive_hierarchical_scan(op, x, inner_names, axis_sizes=inner_sizes)
    # Group total = the last inner device's *inclusive* value; devices with an
    # inner predecessor fold their exclusive prefix in first (op-agnostic:
    # only one device per group contributes to the masked psum).
    inner_first = jnp.logical_not(_nonzero_linear_index(inner_names))
    incl = _where_tree(inner_first, x, op(e_in, x))
    total = incl
    for n, s in zip(inner_names, inner_sizes):
        total = _masked_total(total, n, _axis_size(n, s))
    e_out = exclusive_collective_scan(op, total, outer, axis_size=p_outer)
    # Devices on outer index 0 keep the inner exclusive prefix; inner-first
    # devices of later groups take the group prefix verbatim.
    combined = _where_tree(inner_first, e_out, op(e_out, e_in))
    has_outer_prev = lax.axis_index(outer) > 0
    return _where_tree(has_outer_prev, combined, e_in)


def distributed_blocked_scan(
    op: Op,
    xs_local,
    axis_names: Sequence[str],
    *,
    strategy: str = "reduce_then_scan",
    algorithms: Optional[Sequence[str]] = None,
    axis_sizes: Optional[Sequence[int]] = None,
) -> Any:
    """Local–global–local distributed scan (paper Fig. 6) inside shard_map.

    ``xs_local``: this device's contiguous segment (leading axis K) of the
    global N = K * prod(axis sizes) element array, laid out axis-major.
    Strategy and global circuit per the paper §4.1; the global phase is the
    (possibly hierarchical) collective scan.
    """
    def _exclusive_prefix(partial):
        """Exclusive device prefix of the per-device partials.

        Default (no explicit circuits): every level runs the round-efficient
        exscan directly.  Explicit ``algorithms`` keep the legacy inclusive
        hierarchical scan + shift cascade.
        """
        if algorithms is None:
            return exclusive_hierarchical_scan(
                op, partial, axis_names, axis_sizes=axis_sizes
            )
        g = hierarchical_collective_scan(
            op, partial, axis_names, algorithms=algorithms, axis_sizes=axis_sizes
        )
        return _exclusive_over_hierarchy(g, axis_names, axis_sizes)

    if strategy == "scan_then_map":
        local = _local_inclusive_scan(op, xs_local)          # LP1: local scan
        partial = jax.tree.map(lambda t: t[-1], local)
        prev = _exclusive_prefix(partial)
        has_prev = _nonzero_linear_index(axis_names)
        k = jax.tree.leaves(local)[0].shape[0]
        prev_b = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (k,) + t.shape), prev
        )
        return _where_tree(has_prev, op(prev_b, local), local)
    if strategy == "reduce_then_scan":
        partial = _local_reduce(op, xs_local)                # LP1: local reduce
        prev = _exclusive_prefix(partial)
        has_prev = _nonzero_linear_index(axis_names)
        # Seed the first local element with the exclusive prefix, then scan.
        x0 = jax.tree.map(lambda t: t[:1], xs_local)
        seeded0 = op(jax.tree.map(lambda t: t[None], prev), x0)
        x0 = _where_tree(has_prev, seeded0, x0)
        rest = jax.tree.map(lambda t: t[1:], xs_local)
        seeded = _tree_concat([x0, rest])
        return _local_inclusive_scan(op, seeded)
    raise ValueError(f"unknown strategy {strategy!r}")


def _nonzero_linear_index(axis_names: Sequence[str]):
    """True on every device except the hierarchically-first one."""
    flag = None
    for n in axis_names:
        nz = lax.axis_index(n) > 0
        flag = nz if flag is None else jnp.logical_or(flag, nz)
    return flag


def _exclusive_over_hierarchy(g, axis_names, axis_sizes):
    """Exclusive value for the *flattened* hierarchy: the previous device in
    axis-major order.  Shift along the innermost axis; the first device of
    each inner group instead takes the last device of the previous group,
    which equals the (inclusive) value shifted along the next-outer axis.
    """
    sizes = {
        n: _axis_size(n, None if axis_sizes is None else axis_sizes[i])
        for i, n in enumerate(axis_names)
    }
    inner = axis_names[-1]
    p_in = sizes[inner]
    prev = exclusive_shift(g, inner, axis_size=p_in)
    carry_mask = lax.axis_index(inner) == 0
    # Walk outward: for devices at index 0 of all inner axes so far, the
    # predecessor lives one step back on the next-outer axis (its last slot).
    for depth in range(len(axis_names) - 2, -1, -1):
        ax = axis_names[depth]
        p = sizes[ax]
        # Value of the last inner-slot holder of the previous outer index:
        # g is inclusive per device; the predecessor of (o, 0,...) is
        # (o-1, last,...) whose inclusive value g we need: ppermute over ax
        # from the device with inner index = last.  Since all devices of a
        # group hold different g, first broadcast the group-last g inward.
        last_g = g
        for n in axis_names[depth + 1 :]:
            last_g = _masked_total(last_g, n, sizes[n])
        shifted = exclusive_shift(last_g, ax, axis_size=p)
        prev = _where_tree(carry_mask, shifted, prev)
        carry_mask = jnp.logical_and(carry_mask, lax.axis_index(ax) == 0)
    return prev
