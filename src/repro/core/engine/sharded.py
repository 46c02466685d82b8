"""Sharded series execution across all local devices (``sharded`` backend).

One long registration series runs as a single jitted ``shard_map`` launch
over a 1-D mesh of the local devices — the first execution path where plan
rounds, stealing telemetry and the runtime all cross the device boundary:

  phase 1  per-shard reduce.  Each device reduces the *core* of its static
           shard; the halo region around every shard boundary is split into
           fixed-size blocks whose partials both neighbours compute
           redundantly (one ppermute halo exchange each way), and the PR-3
           stealing protocol decides at run time which side's total each
           block joins: host callbacks (``jax.experimental.io_callback``)
           claim blocks from a shared boundary :class:`~repro.core.
           work_stealing._Gap` ledger, so the first shard to finish its
           core drains more of the no-man's-land — the paper's Algorithm-1
           greedy loop promoted to the device level.
  phase 2  cross-shard *round-efficient exclusive scan* over the shard
           totals: the Träff 2025 exscan schedule
           (``core/circuits.exscan_circuit`` lowered through
           ``lower_collective(..., registers=2)``) — exactly
           ceil(log2 devices) ppermute rounds, no shift round.
  phase 3  fused seeded apply: every device folds seed + exclusive prefix
           into one masked local scan of its halo-extended rows; outputs
           for rows a neighbour claimed come back over one overhang
           ppermute and a position select.

Everything runs in the packed + identity-flag domain of
``kernels/_tiling`` (one ``(rows, D+1)`` array per device), which makes
``where=`` masks, seeds, tail padding and the exscan's identity
initialisation uniform — and makes any claim outcome value-exact for
exactly-associative operators: claims move *grouping boundaries* only,
never element order.

The claim protocol is deadlock-free by construction: claim attempts never
block (single ``_Gap``-lock critical sections), and the final block
partition is read only after a neighbour token exchange (ppermute of
values data-dependent on the neighbours' last claim attempts) proves both
drainers of each adjacent gap have finished.  ``finalize`` then assigns
any unclaimed remainder deterministically, so a dropped or elided
callback degrades balance, never correctness.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.sync import sync_point

Op = Callable[[Any, Any], Any]

AXIS = "shard"

#: Smallest per-device shard (rows) for which boundary stealing is enabled:
#: below this the halo blocks would be single rows and the claim traffic
#: costs more than the imbalance it removes.
MIN_STEAL_SHARD = 16

#: Default number of boundary blocks per shard gap (must be even: half the
#: blocks come from each neighbour's static side).
DEFAULT_GAP_BLOCKS = 4


# ---------------------------------------------------------------------------
# host-side boundary ledger
# ---------------------------------------------------------------------------


class BoundaryLedger:
    """Shared-``_Gap`` claim ledger for the D-1 shard boundaries.

    Gap ``g`` (between shards ``g`` and ``g+1``) holds ``blocks`` claimable
    block indices ``[0, blocks)``; ``border = blocks // 2`` marks the static
    shard boundary inside it.  Shard ``g`` drains from the left
    (``take_left``), shard ``g+1`` from the right (``take_right``), so the
    final partition is always a prefix/suffix split.  Claims past the border
    count as cross-shard steals, mirroring ``_Gap.border`` accounting in the
    thread-level protocol.
    """

    def __init__(self, num_gaps: int, blocks: int):
        from ..work_stealing import _Gap

        self.blocks = blocks
        self.border = blocks // 2  # analysis: allow[THR002] ctor precedes publication
        self.gaps = [_Gap(0, blocks, border=self.border) for _ in range(num_gaps)]
        self.arrival: Dict[int, float] = {}   # shard -> core-finish host time
        self.cross_steals = 0
        self.forced = 0
        self.finalized = [False] * num_gaps
        self._lock = threading.Lock()

    def _neighbour_rate_locked(self, shard: int, now: float) -> float:
        """Arrival-time proxy for a neighbour's sec/op rate: a shard that has
        not reached its boundary yet is the straggler (large rate).  Caller
        holds ``_lock`` (the ``arrival`` map is lock-guarded)."""
        t = self.arrival.get(shard)
        if t is None:
            return float("inf")
        return max(now - t, 0.0)

    def attempt(self, shard: int) -> int:
        """One greedy claim attempt by ``shard`` (Algorithm-1 step at the
        device level).  Returns the number of blocks claimed (0 or 1)."""
        from ..work_stealing import _steal_direction

        d = int(shard)
        now = time.monotonic()
        with self._lock:
            sync_point("shard.gap.seat", "write",
                       var="shard.ledger", lock="shard.ledger.lock")
            if d not in self.arrival:
                self.arrival[d] = now
            rate_l = self._neighbour_rate_locked(d - 1, now)
            rate_r = self._neighbour_rate_locked(d + 1, now)
        lg = self.gaps[d - 1] if d >= 1 else None
        rg = self.gaps[d] if d < len(self.gaps) else None
        size_l = lg.size() if lg is not None else 0
        size_r = rg.size() if rg is not None else 0
        if size_l <= 0 and size_r <= 0:
            return 0
        side = _steal_direction(rate_l, rate_r, size_l, size_r)
        if side == "L":
            idx = lg.take_right()
            cross = idx is not None and idx < self.border
        else:
            idx = rg.take_left()
            cross = idx is not None and idx >= self.border
        if idx is None:
            return 0
        with self._lock:
            sync_point("shard.gap.claim", "write",
                       var="shard.ledger", lock="shard.ledger.lock")
            if cross:
                self.cross_steals += 1
        return 1

    def _finalize_gap(self, g: int) -> None:
        """Deterministically assign any unclaimed remainder (idempotent).

        Reached only when claim callbacks were elided or lost: both drainers
        have proven (token exchange) they issued all attempts, so a
        remainder means dropped calls — give it to the left side.  Any
        consistent split is value-correct; only balance degrades.
        """
        if g < 0 or g >= len(self.gaps):
            return
        with self._lock:
            sync_point("shard.gap.finalize", "read",
                       var="shard.ledger", lock="shard.ledger.lock")
            if self.finalized[g]:
                return
        gap = self.gaps[g]
        while gap.take_left() is not None:
            with self._lock:
                self.forced += 1
        with self._lock:
            sync_point("shard.gap.finalize", "write",
                       var="shard.ledger", lock="shard.ledger.lock")
            self.finalized[g] = True

    def claims(self, shard: int) -> np.ndarray:
        """Final (k_left, k_right) for ``shard`` — blocks of its left/right
        gap owned by the gap's *left* side.  Virtual edge gaps report the
        static border.  Call only after the neighbour token exchange."""
        d = int(shard)
        with self._lock:
            already = (d - 1 < 0 or self.finalized[d - 1]) and (
                d >= len(self.gaps) or self.finalized[d]
            )
        if not already:
            self._finalize_gap(d - 1)
            self._finalize_gap(d)
        kl = self.gaps[d - 1].taken_left if d >= 1 else self.border
        kr = self.gaps[d].taken_left if d < len(self.gaps) else self.border
        return np.asarray([kl, kr], dtype=np.int32)

    def claim_counts(self) -> List[Tuple[int, int]]:
        return [(g.taken_left, g.taken_right) for g in self.gaps]


class _LedgerSlot:
    """Mutable holder the compiled callbacks close over, so one compiled
    ``shard_map`` launch can serve many calls, each with a fresh ledger."""

    def __init__(self):
        self.ledger: Optional[BoundaryLedger] = None
        self.lock = threading.Lock()

    def attempt(self, shard, _dep) -> np.int32:
        led = self.ledger
        return np.int32(led.attempt(shard) if led is not None else 0)

    def claims(self, shard, _dep) -> np.ndarray:
        led = self.ledger
        if led is None:
            b = DEFAULT_GAP_BLOCKS // 2
            return np.asarray([b, b], dtype=np.int32)
        return led.claims(shard)


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedStats:
    """Telemetry of the most recent sharded execution."""

    devices: int
    n: int
    shard_rows: int            # padded rows per device
    halo: int                  # halo rows each side of a boundary
    gap_blocks: int            # claimable blocks per boundary gap
    phase2_rounds: int         # executed exscan ppermute rounds
    phase2_algorithm: str
    boundary_claims: List[Tuple[int, int]]  # per gap: (left, right) blocks
    cross_steals: int          # blocks claimed past the static border
    forced_blocks: int         # remainder blocks assigned by finalize
    stealing: bool
    phase_seconds: Dict[str, float]


#: Stats of the most recent ``sharded`` execution (None before the first).
last_stats: Optional[ShardedStats] = None


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------


def _shard_geometry(
    n: int, devices: int, num_blocks: Optional[int] = None
) -> Tuple[int, int, int, int]:
    """(padded_n, rows_per_shard, halo, gap_blocks) for an n-row series."""
    k = -(-n // devices)  # ceil
    n_pad = k * devices
    if k < MIN_STEAL_SHARD:
        return n_pad, k, 0, 0
    blocks = int(num_blocks) if num_blocks else DEFAULT_GAP_BLOCKS
    blocks = max(2, blocks - (blocks % 2))
    bs = max(1, k // (2 * blocks))
    halo = (blocks // 2) * bs
    return n_pad, k, halo, blocks


def default_mesh(devices: Optional[int] = None):
    """1-D mesh over the first ``devices`` local devices."""
    import jax
    from jax.sharding import Mesh

    avail = jax.devices()
    d = len(avail) if devices is None else min(int(devices), len(avail))
    return Mesh(np.asarray(avail[:d]), (AXIS,))


# ---------------------------------------------------------------------------
# traced shard body
# ---------------------------------------------------------------------------


def _id_row(width: int, dtype):
    """The lifted-monoid identity: zero values, identity flag 1."""
    import jax.numpy as jnp

    row = jnp.zeros((1, width), dtype)
    return row.at[0, -1].set(1.0)


def _fold_rows(pop: Op, rows):
    """Left-to-right fold of (m, D+1) rows into one (1, D+1) row."""
    from jax import lax

    return lax.associative_scan(pop, rows, axis=0)[-1:]


def _build_sharded_fn(pop, devices, k, halo, blocks, width, dtype, slot,
                      stealing):
    """Trace-time factory for the jitted shard_map body (cached per key)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import io_callback

    from ..distributed import exclusive_collective_scan

    p = devices
    bs = (2 * halo) // blocks if blocks else 0
    fwd = [(i, i + 1) for i in range(p - 1)]   # send right
    bwd = [(i + 1, i) for i in range(p - 1)]   # send left
    i32 = jnp.int32

    def body(x, seed_row):
        my = lax.axis_index(AXIS)
        ident = _id_row(width, dtype)
        if halo == 0:
            # Degenerate geometry: no boundary gaps, static shards only.
            total = _fold_rows(pop, x)
            e = exclusive_collective_scan(
                pop, total, AXIS, axis_size=p, init=ident
            )
            seeded = pop(seed_row, e)
            scanned = lax.associative_scan(pop, x, axis=0)
            return pop(jnp.broadcast_to(seeded, (k, width)), scanned)

        # --- halo exchange: left gap rows = neighbour tail + own head -----
        from_left = lax.ppermute(x[k - halo:], AXIS, perm=fwd)
        from_right = lax.ppermute(x[:halo], AXIS, perm=bwd)
        ext = jnp.concatenate([from_left, x, from_right], axis=0)

        # --- phase 1: core reduce + redundant boundary-block partials -----
        core = _fold_rows(pop, ext[2 * halo: k])
        bp_left = jax.vmap(lambda b: _fold_rows(pop, b)[0])(
            ext[: 2 * halo].reshape(blocks, bs, width)
        )
        bp_right = jax.vmap(lambda b: _fold_rows(pop, b)[0])(
            ext[k: k + 2 * halo].reshape(blocks, bs, width)
        )

        if stealing:
            # Claim loop: ``blocks`` chained attempts, data-dependent on the
            # finished core reduce (the "I reached my boundary" signal).
            # One budget covers both adjacent gaps: a straggler's neighbour
            # can still claim a whole shared gap (all its attempts steer to
            # one side), and any blocks left when both budgets are spent
            # fall to the deterministic finalize — balance, not correctness.
            dep = core[0, -1].astype(i32) * 0
            for _ in range(blocks):
                got = io_callback(
                    slot.attempt, jax.ShapeDtypeStruct((), i32),
                    my, dep, ordered=False,
                )
                dep = dep + got
            # Token exchange: my neighbours' dep values arriving proves both
            # drainers of each adjacent gap issued all their attempts.
            tok_l = lax.ppermute(dep, AXIS, perm=fwd)
            tok_r = lax.ppermute(dep, AXIS, perm=bwd)
            ks = io_callback(
                slot.claims, jax.ShapeDtypeStruct((2,), i32),
                my, dep + tok_l + tok_r, ordered=False,
            )
            kl, kr = ks[0], ks[1]
        else:
            kl = kr = i32(blocks // 2)

        # --- assemble this shard's total over its claimed range -----------
        acc = ident
        for j in range(blocks):
            take = j >= kl
            acc = jnp.where(take, pop(acc, bp_left[j: j + 1]), acc)
        acc = pop(acc, core)
        for j in range(blocks):
            take = j < kr
            acc = jnp.where(take, pop(acc, bp_right[j: j + 1]), acc)

        # --- phase 2: Träff exscan over shard totals ----------------------
        e = exclusive_collective_scan(pop, acc, AXIS, axis_size=p, init=ident)
        seeded = pop(seed_row, e)

        # --- phase 3: masked local scan of the claimed range --------------
        gidx = my * k - halo + jnp.arange(k + 2 * halo)
        bl = my * k - halo + kl * bs
        br = (my + 1) * k - halo + kr * bs
        active = (gidx >= bl) & (gidx < br)
        flags = jnp.where(active, ext[:, -1], jnp.asarray(1.0, dtype))
        ext_m = jnp.concatenate([ext[:, :-1], flags[:, None]], axis=1)
        scanned = lax.associative_scan(pop, ext_m, axis=0)
        out_ext = pop(jnp.broadcast_to(seeded, scanned.shape), scanned)

        # --- overhang exchange: rows a neighbour scanned ------------------
        recv_l = lax.ppermute(out_ext[k + halo:], AXIS, perm=fwd)
        recv_r = lax.ppermute(out_ext[:halo], AXIS, perm=bwd)
        out = out_ext[halo: halo + k]
        g_head = my * k + jnp.arange(halo)
        head = jnp.where((g_head < bl)[:, None], recv_l, out[:halo])
        g_tail = (my + 1) * k - halo + jnp.arange(halo)
        tail = jnp.where((g_tail >= br)[:, None], recv_r, out[k - halo:])
        return jnp.concatenate([head, out[halo: k - halo], tail], axis=0)

    return body


#: Compiled shard_map launch cache: op identity is part of the key, so a
#: stable operator (module function / bound method) warm-starts across
#: calls and series — the same contract as the engine's plan cache.
_fn_cache: Dict[Tuple, Any] = {}
_fn_cache_lock = threading.Lock()


def _get_sharded_fn(op, spec, mesh, k, halo, blocks, width, dtype, stealing):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels._tiling import lift_masked, packed_op

    devices = mesh.shape[AXIS]
    try:
        key = (op, spec, devices, tuple(mesh.devices.flat), k, halo, blocks,
               width, str(dtype), stealing)
        hash(key)
    except TypeError:
        key = None
    with _fn_cache_lock:
        hit = _fn_cache.get(key) if key is not None else None
    if hit is not None:
        return hit
    slot = _LedgerSlot()
    pop = lift_masked(packed_op(op, spec))
    body = _build_sharded_fn(pop, devices, k, halo, blocks, width, dtype,
                             slot, stealing)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P()), out_specs=P(AXIS),
        check_vma=False,
    ))
    entry = (fn, slot)
    if key is not None:
        with _fn_cache_lock:
            _fn_cache[key] = entry
    return entry


# ---------------------------------------------------------------------------
# backend entry point
# ---------------------------------------------------------------------------


def exec_sharded(
    op: Op,
    plan,
    xs,
    *,
    devices: Optional[int] = None,
    mesh=None,
    num_blocks: Optional[int] = None,
    seed: Any = None,
    where=None,
    stealing: bool = True,
    **_,
) -> Tuple[Any, Any]:
    """Multi-device sharded scan; returns ``(ys, total=None)``.

    ``plan`` is ignored: the cross-shard phase always runs the Träff exscan
    schedule (that round-efficiency is the point of the backend).
    ``mesh`` pins the device mesh (sessions build one per series);
    ``devices`` caps the mesh size when no mesh is given.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels._tiling import pack_element, pack_leaves, unpack_leaves
    from .decoupled_backend import stack_elements

    global last_stats

    if isinstance(xs, list):
        stacked = stack_elements(xs)
        if stacked is None:
            raise ValueError(
                "sharded backend needs stackable array elements; got a list "
                "the operator cannot be batched over — use "
                "element/worksteal/hierarchical"
            )
        ys, total = exec_sharded(
            op, plan, stacked, devices=devices, mesh=mesh,
            num_blocks=num_blocks, seed=seed, where=where, stealing=stealing,
        )
        n = len(xs)
        return [jax.tree.map(lambda t, i=i: t[i], ys) for i in range(n)], total

    t0 = time.perf_counter()
    if mesh is None:
        mesh = default_mesh(devices)
    p = mesh.shape[AXIS]

    x2, spec = pack_leaves(xs)
    n = x2.shape[0]
    # Identity-flag lane: dynamic where= masks and tail padding ride along.
    if where is not None:
        if len(where) != n:
            raise ValueError(f"where mask length {len(where)} != n {n}")
        flags = jnp.asarray(
            [0.0 if bool(v) else 1.0 for v in where], x2.dtype
        ).reshape(n, 1)
    else:
        flags = jnp.zeros((n, 1), x2.dtype)
    x2 = jnp.concatenate([x2, flags], axis=1)
    width = x2.shape[1]
    dtype = x2.dtype

    n_pad, k, halo, blocks = _shard_geometry(n, p, num_blocks)
    if n_pad != n:
        pad = jnp.zeros((n_pad - n, width), dtype).at[:, -1].set(1.0)
        x2 = jnp.concatenate([x2, pad], axis=0)

    if seed is not None:
        seed_row = jnp.concatenate(
            [pack_element(seed, spec), jnp.zeros((1,), dtype)], axis=0
        )[None]
    else:
        seed_row = np.zeros((1, width))
        seed_row[0, -1] = 1.0
        seed_row = jnp.asarray(seed_row, dtype)

    steal = bool(stealing) and halo > 0 and p > 1
    fn, slot = _get_sharded_fn(op, spec, mesh, k, halo, blocks, width, dtype,
                               steal)

    from ..circuits import exscan_num_rounds

    t1 = time.perf_counter()
    with slot.lock:
        slot.ledger = BoundaryLedger(p - 1, blocks) if steal else None
        y2 = fn(x2, seed_row)
        jax.block_until_ready(y2)
        ledger = slot.ledger
        slot.ledger = None
    t2 = time.perf_counter()

    y2 = y2[:n, :-1]
    ys = unpack_leaves(y2, spec)
    last_stats = ShardedStats(
        devices=p,
        n=n,
        shard_rows=k,
        halo=halo,
        gap_blocks=blocks,
        phase2_rounds=exscan_num_rounds(p),
        phase2_algorithm="exscan",
        boundary_claims=ledger.claim_counts() if ledger else [],
        cross_steals=ledger.cross_steals if ledger else 0,
        forced_blocks=ledger.forced if ledger else 0,
        stealing=steal,
        phase_seconds={
            "setup": t1 - t0,
            "execute": t2 - t1,
            "unpack": time.perf_counter() - t2,
        },
    )
    return ys, None


from .backends import register_backend  # noqa: E402  (import cycle: registry)

register_backend("sharded", exec_sharded)
