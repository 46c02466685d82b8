"""Persistent registration runtime: series sessions over the shared pool.

The paper's acquisition setting is *streaming* — 4,096 frames over ten
seconds, series after series — yet ``register_series`` used to be a one-shot
batch call that threw every piece of scan state away at return.  This module
makes the runtime resident:

    session = open_series(cfg)            # a tenant of the shared WorkerPool
    session.feed(chunk)                   # ingest + function A + seeded scan
    session.feed(chunk)                   #   ... as frames arrive
    res = session.result()                # SeriesResult for everything so far
    res2 = session.extend(late_frames)    # O(new) fold, no recompute
    session.close()

**Incremental scan.**  The scan operator is associative, so a session only
has to retain the running cumulative element phi_{0,m} (plus per-chunk
reduce summaries for recovery): a suffix of ``k`` new frames costs the
``k`` function-A pair registrations plus a *seeded* engine scan of the
``k`` new elements — O(new) operator applications and an O(log S)
cross-segment phase, against the O(n + new) full recompute
(``benchmarks/bench_serve.py`` gates the ratio).  ``extend`` after
``result()`` is explicitly supported: a frame arriving late folds in
without recomputing the series.

**Multi-tenancy.**  All sessions execute on one injected
:class:`~repro.runtime.scheduler.WorkerPool` (process-wide shared pool by
default).  A session's scan runs inside ``pool.tenant()``: the dispatcher
sees the pool's occupancy and tenant count, shrinks the per-series worker
budget fairly, and shifts small series to the work-optimal sequential chain
when the pool is saturated (``engine/cost.py:POOL_BUSY_OCCUPANCY``).

**Telemetry isolation.**  Each session records into a *namespaced* channel
(``get_telemetry(name, session=...)``): two concurrent series with
same-named operators but different image sizes no longer share cost /
imbalance EMAs (they used to poison each other's dispatch).  ``close()``
releases the channel.

**Frame residency.**  Function B only ever touches frame 0 (every refined
pair is (0, k)), the boundary frame of the previous chunk, and the frames
of the chunk being scanned — so after each feed the session evicts
everything else (:class:`_FrameStore`).  A 4,096-frame session holds two
frames, not four thousand.

**Recovery.**  ``checkpoint()`` snapshots the scan state (cumulative
deformations, boundary frames, per-pair cost history, telemetry prime)
through :class:`~repro.checkpoint.checkpointer.Checkpointer`;
``SeriesSession.restore`` rebuilds a mid-series session from the latest
snapshot and continues feeding.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.deformation import (
    Deformation,
    compose,
    compose_batched,
    identity_deformation,
)
from repro.core.engine import (
    SHARDED_MIN_DEVICES,
    Dispatch,
    dispatch as cost_dispatch,
    get_telemetry,
    op_batchable_from,
    op_concurrency_from,
    pool_aware_workers,
    release_telemetry,
    scan as engine_scan,
    worker_budget,
)
from repro.core.engine.sharded import AXIS, default_mesh
from repro.core.registration import (
    RegElement,
    RegistrationConfig,
    RegistrationOperator,
    SeriesRegistrar,
    lane_work,
    register_pair,
)
from repro.runtime import spans
from repro.runtime.compile_cache import get_compile_cache, set_cache_dir
from repro.runtime.scheduler import get_default_pool


@dataclasses.dataclass(frozen=True)
class RegisterSeriesConfig:
    """Knobs for :func:`repro.register_series` and :class:`SeriesSession`
    (defaults follow the paper)."""

    registration: RegistrationConfig = RegistrationConfig()
    refine: bool = True                  # function B refinement (paper's B)
    backend: Optional[str] = None        # None -> cost-model dispatch
    algorithm: Optional[str] = None
    num_segments: Optional[int] = None   # hierarchical: node-local segments
    num_threads: Optional[int] = None    # threads (per segment, if hier)
    stealing: bool = True
    cross_steal: Optional[bool] = None   # inter-segment stealing; None ->
                                         # dispatcher rule (telemetry imbalance)
    workers: Optional[int] = None
    devices: Optional[int] = None        # local devices for the sharded
                                         # multi-device scan; None ->
                                         # jax.device_count() at session init
    skip_tol: Optional[float] = None     # fused guess check threshold
    fused_ncc: Optional[bool] = None     # route checks through warp_ncc
    telemetry_name: str = "registration_B"
    prefetch_depth: int = 1              # streaming-ingest lookahead chunks

    def __post_init__(self):
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )


@dataclasses.dataclass
class SeriesResult:
    """Everything :func:`repro.register_series` / ``session.result()``
    produce.

    ``timings`` maps pipeline stage -> cumulative wall-clock **seconds**
    spent in that stage over the session's whole life (a ``result()``
    mid-stream reports the seconds so far, and later results include the
    earlier work).  Each stage but ``compile`` is also a host span of the
    profiler's trace, named in brackets:

    * ``ingest``     — a fed chunk made a device array and waited for
      (``repro.feed.ingest``);
    * ``compile``    — XLA trace/compile time for the vmapped function-A
      cohorts (kept out of ``preprocess`` so cost telemetry and speedup
      numbers are not poisoned by one-off compilation);
    * ``preprocess`` — function A proper: batched pairwise registration of
      new frame pairs (paper §3's element construction), up to the return
      of their iteration counts (``repro.fn_a``);
    * ``scan``       — the (.)_B prefix scan over elements (work-stealing /
      hierarchical / sequential, whichever the dispatcher chose) as host
      time (``repro.scan``).  It does not wait for function B's device
      work at its end: the device time of the applications still queued
      then lands in the next stage that blocks, the next feed's
      ``preprocess`` or ``result()``'s ``compose``.  Nor is it issue time
      alone: an application issued while the device's queue is full
      waits for room;
    * ``compose``    — batching per-element deformations into the stacked
      ``Deformation`` output (``repro.result``).

    A plain dataclass of already-materialised values: safe to read from
    any thread once returned, and never mutated by the session afterwards
    (``timings`` is a copy).
    """

    deformations: Deformation            # batched phi_{0,i}, identity at i=0
    elements: List[RegElement]           # scan output, N-1 entries
    timings: Dict[str, float]            # per-stage wall seconds (see above)
    backend: str                         # backend that executed the scan
    op_telemetry: Dict[str, float]       # adapter cost statistics
    scan_stats: Optional[Any] = None     # HierStats when hierarchical ran
    dispatch: Optional[Dispatch] = None  # cost-model decision of the last
                                         # scan (None when cfg.backend pins)
    compile_cache: Optional[Dict[str, float]] = None  # session hit/miss/secs

    @property
    def n_frames(self) -> int:
        return len(self.elements) + 1

    def report(self) -> str:
        lines = [
            f"registered {self.n_frames} frames via backend={self.backend!r}"
        ]
        if self.dispatch is not None:
            lines.append(f"  dispatch: {self.dispatch.reason}")
        total = sum(self.timings.values())
        for stage, secs in self.timings.items():
            lines.append(f"  {stage:<12} {secs:8.3f}s")
        lines.append(f"  {'total':<12} {total:8.3f}s")
        tel = self.op_telemetry
        if tel.get("calls"):
            lines.append(
                f"  operator: {tel['calls']:.0f} calls, "
                f"mean {tel['mean_s'] * 1e3:.1f} ms, "
                f"max {tel['max_s'] * 1e3:.1f} ms "
                f"(imbalance {tel['imbalance']:.1f}x)"
            )
        cc = self.compile_cache
        if cc is not None and (cc.get("hits") or cc.get("misses")):
            lines.append(
                f"  compile cache: {cc.get('hits', 0):.0f} hits, "
                f"{cc.get('misses', 0):.0f} misses, "
                f"{cc.get('compile_s', 0.0):.3f}s compiling"
            )
        if self.scan_stats is not None:
            st = self.scan_stats
            ph = st.phase_seconds
            if hasattr(st, "devices"):  # ShardedStats
                lines.append(
                    f"  sharded: {st.devices} devices x {st.shard_rows} rows; "
                    f"phase-2 {st.phase2_rounds} rounds "
                    f"({st.phase2_algorithm}); "
                    f"{st.cross_steals} cross-shard steals; "
                    + ", ".join(f"{k}={v:.3f}s" for k, v in ph.items())
                )
                return "\n".join(lines)
            lines.append(
                f"  hierarchical: {st.num_segments} segments x "
                f"{st.threads_per_segment} threads; "
                + ", ".join(f"{k}={v:.3f}s" for k, v in ph.items())
            )
            if getattr(st, "cross_steal", False):
                per_seg = ",".join(str(k) for k in st.inter_segment_steals)
                lines.append(
                    "  cross-segment steals: "
                    f"{st.total_inter_segment_steals()} "
                    f"(per segment: {per_seg})"
                    + ("; cost-history segment sizing"
                       if st.rebalanced else "")
                )
        return "\n".join(lines)


class _FrameStore:
    """Frame access by *global* series index with O(1) residency.

    Registrar-compatible (``shape``, integer indexing and ``devices()``,
    as on a ``jax.Array``), so function B can keep addressing
    ``frames[a.i]`` / ``frames[b.k]`` by global index while the session
    retains only the frames an incremental scan can touch:
    frame 0 and the chunk boundary (everything else is evicted after its
    chunk has been folded in).  Touching an evicted frame is a protocol
    bug, not a recoverable condition — it raises with the index.

    Across chips each frame has a *home* (the chip that registers it) and
    may have copies on other chips: frame 0 on every chip, a halo frame
    at each chip boundary (placed by the session), and whatever function
    B fetches with :meth:`on` (counted in ``moved_bytes``).
    """

    def __init__(self):
        self._frames: Dict[int, jax.Array] = {}
        self._copies: Dict[tuple, jax.Array] = {}    # (index, device) -> copy
        self._copy_lock = threading.Lock()
        self.moved_bytes = 0     # bytes function B copied between chips
        self._n = 0
        self._hw: tuple = ()

    @property
    def n(self) -> int:
        return self._n

    @property
    def shape(self) -> tuple:
        return (self._n,) + tuple(self._hw)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i) -> jax.Array:
        try:
            return self._frames[int(i)]
        except KeyError:
            raise IndexError(
                f"frame {int(i)} was evicted from the session's frame "
                f"window (resident: {sorted(self._frames)}); an incremental "
                "scan should only touch frame 0, the chunk boundary and the "
                "current chunk"
            ) from None

    def last(self) -> Optional[jax.Array]:
        return self._frames.get(self._n - 1)

    def devices(self) -> set:
        """The devices that hold the resident frames (homes, not copies)."""
        return set().union(*(f.devices() for f in self._frames.values()))

    def resident(self, i, device) -> bool:
        """Whether frame ``i`` is on ``device``, as its home or a copy."""
        f = self._frames.get(int(i))
        return f is not None and (device in f.devices()
                                  or (int(i), device) in self._copies)

    def home(self, i) -> Any:
        """The device that holds frame ``i``'s home array."""
        return next(iter(self[i].devices()))

    def place(self, i, device) -> int:
        """Make frame ``i`` resident on ``device`` ahead of its use;
        returns the bytes copied."""
        f = self[i]
        key = (int(i), device)
        with self._copy_lock:
            if device in f.devices() or key in self._copies:
                return 0
            self._copies[key] = jax.device_put(f, device)
        return int(f.nbytes)

    def on(self, i, device) -> tuple:
        """Frame ``i`` on ``device``, and the bytes copied to get it there:
        its home array, a placed copy, or a new copy that stays resident
        (counted in ``moved_bytes``)."""
        f = self[i]
        if device in f.devices():
            return f, 0
        key = (int(i), device)
        with self._copy_lock:
            c = self._copies.get(key)
            if c is not None:
                return c, 0
            c = self._copies[key] = jax.device_put(f, device)
            self.moved_bytes += int(f.nbytes)
        return c, int(f.nbytes)

    def append_chunk(self, chunk: jax.Array) -> None:
        self.append_frames([chunk[i] for i in range(chunk.shape[0])])

    def append_frames(self, frames: List[jax.Array]) -> None:
        """Append frames that already sit on their home devices."""
        for i, f in enumerate(frames):
            self._frames[self._n + i] = f
        self._n += len(frames)
        self._hw = tuple(frames[0].shape)

    def evict(self, keep) -> None:
        keep = set(keep)
        self._frames = {i: f for i, f in self._frames.items() if i in keep}
        with self._copy_lock:
            self._copies = {k: f for k, f in self._copies.items()
                            if k[0] in keep}

    def restore(self, n: int, frames: Dict[int, jax.Array]) -> None:
        self._n = n
        self._frames = dict(frames)
        self._copies = {}
        if frames:
            self._hw = tuple(next(iter(frames.values())).shape)


def _shard_rows(a: jax.Array) -> List[jax.Array]:
    """The blocks of an array split along its first axis, in order, each
    on its own device."""
    shards = sorted(a.addressable_shards, key=lambda s: s.index[0].start or 0)
    return [s.data for s in shards]


def _fn_a_shards(mesh: Mesh, cfg: RegistrationConfig):
    """Function A sharded by pair: one program over ``mesh`` in which each
    chip registers its block of frames against their predecessors (its
    halo frame, then its own frames) as one vmapped cohort.  There is no
    collective, so each chip's while loops stop on their own.  Compiled
    as ``jit_fn_a_shards``, the name the device trace shows."""
    spec = PartitionSpec(mesh.axis_names[0])

    def shard(halo, tmps):
        refs = jnp.concatenate([halo, tmps[:-1]], axis=0)
        return jax.vmap(lambda r, t: register_pair(r, t, None, cfg))(refs, tmps)

    def fn_a_shards(halo, tmps):
        return jax.shard_map(shard, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec, check_vma=False)(halo, tmps)

    return fn_a_shards


@dataclasses.dataclass
class _ChunkSummary:
    """Retained per-feed reduce summary (recovery / introspection)."""

    first_elem: int          # global index of the first element folded in
    n_elems: int
    seconds: float           # scan-stage wall time of this feed
    ops: int                 # operator applications this feed recorded


def _unflatten_keys(flat: Dict[str, Any]) -> Dict[str, Any]:
    """Rebuild a nested dict from '/'-joined checkpoint leaf keys."""
    out: Dict[str, Any] = {}
    for key, value in flat.items():
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return out


_session_ids = itertools.count()


class SeriesSession:
    """One resident series: feed chunks, read results, extend, recover.

    **Thread-safety.**  A series is one ordered stream: concurrent
    ``feed``/``extend`` calls on the *same* session are serialized by an
    internal lock (their completion order is then unspecified, which is
    almost never what a caller wants — submit in order from one thread,
    or route through :class:`repro.serving.RegistrationFrontend`, which
    guarantees per-session FIFO).  Many sessions on the shared pool are
    safe and intended.  ``result()`` may race a concurrent ``feed`` only
    in that it reports whichever prefix has fully folded in.

    **Blocking.**  ``feed``/``result``/``extend``/``checkpoint`` all run
    their compute synchronously on the calling thread (plus pool workers)
    and return only when done — there is no internal queue.  The serving
    front end is the async layer.

    **Units.**  All timing fields are wall-clock seconds (see
    :class:`SeriesResult` for the per-stage breakdown).
    """

    def __init__(
        self,
        cfg: Optional[RegisterSeriesConfig] = None,
        *,
        pool=None,
        session_id: Optional[str] = None,
        checkpoint_dir: Optional[str] = None,
        compile_cache_dir: Optional[str] = None,
    ):
        self.cfg = cfg if cfg is not None else RegisterSeriesConfig()
        self.id = session_id or f"series{next(_session_ids)}"
        if compile_cache_dir is not None:
            # The plan store, and XLA's persistent cache unless
            # $JAX_COMPILATION_CACHE_DIR places it; the in-process
            # executable cache works regardless.
            set_cache_dir(compile_cache_dir)
        self.pool = pool if pool is not None else get_default_pool()
        self.telemetry = get_telemetry(
            self.cfg.telemetry_name, session=self.id
        )
        self._store = _FrameStore()
        self._elements: List[RegElement] = []   # cumulative phi_{0,k}
        self._pair_iters: List[int] = []        # function-A cost history
        self._summaries: List[_ChunkSummary] = []
        self._timings: Dict[str, float] = {
            "ingest": 0.0, "preprocess": 0.0, "scan": 0.0, "compose": 0.0,
            "compile": 0.0,
        }
        # This session's view of the process-wide executable cache.
        self._compile: Dict[str, float] = {
            "hits": 0, "misses": 0, "compile_s": 0.0,
        }
        self._backend_used: Optional[str] = None
        self._dispatch = None
        self._scan_stats = None
        # Pin the device mesh once: every suffix scan of this series runs
        # on the same devices, so sharded executables (and their boundary
        # ledgers) are reused across feeds instead of re-traced per chunk.
        self._devices = max(1, min(
            self.cfg.devices if self.cfg.devices is not None
            else jax.device_count(),
            jax.device_count(),
        ))
        # Frames across chips: each chunk is split by frame over the same
        # devices, and each chip registers its own frames.
        mesh = default_mesh(self._devices) if self._devices > 1 else None
        self._mesh = mesh if self._devices >= SHARDED_MIN_DEVICES else None
        self._chips = tuple(mesh.devices.flat) if mesh else None
        self._chip_sharding = (
            NamedSharding(mesh, PartitionSpec(AXIS)) if mesh else None
        )
        self._steals = 0
        self._placed_bytes = 0
        self._pre_seconds = 0.0
        self._pre_pairs = 0
        self._feed_lock = threading.Lock()
        self._closed = False
        self._ckpt = (
            Checkpointer(checkpoint_dir, async_save=False)
            if checkpoint_dir is not None else None
        )

    # ------------------------------------------------------------ queries

    @property
    def n_frames(self) -> int:
        return self._store.n

    @property
    def n_elements(self) -> int:
        return len(self._elements)

    @property
    def summaries(self) -> List[_ChunkSummary]:
        return list(self._summaries)

    def traffic(self) -> Dict[str, int]:
        """Totals across chips over the session's life: ``steals``, the
        elements function B's workers claimed across a chip boundary;
        ``moved_bytes``, the frame bytes function B copied between chips;
        ``placed_bytes``, the frame bytes ``feed`` placed (the chunk's
        layout, halo frames, frame 0 on every chip)."""
        return {"steals": self._steals,
                "moved_bytes": self._store.moved_bytes,
                "placed_bytes": self._placed_bytes}

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(f"session {self.id!r} is closed")

    # --------------------------------------------------------------- feed

    def feed(self, chunk) -> "SeriesSession":
        """Ingest one chunk of frames and fold it into the running scan.

        Runs function A on the chunk's consecutive pairs (including the
        pair spanning the previous chunk's boundary), then scans the new
        elements *seeded* with the retained cumulative element — O(new)
        operator applications however long the series already is.  Empty
        chunks (ragged stream tails) are skipped.

        Blocking: returns after the chunk has fully folded in (preprocess
        + scan), typically the most expensive call on a session.  Safe to
        call from one thread at a time; overlapping callers serialize on
        the session's feed lock.
        """
        self._check_open()
        with spans.serving(self.id), spans.span("feed", frames=len(chunk)), \
                self._feed_lock:
            with self._stage("ingest", "feed.ingest"):
                chunk = jnp.asarray(chunk)
                jax.block_until_ready(chunk)
            if chunk.shape[0] == 0:
                return self
            new_elems = (self._fold_chunk_chips(chunk) if self._chips
                         else self._fold_chunk(chunk))
            if new_elems:
                self._scan_suffix(new_elems)
            # O(1) residency: only frame 0 and the boundary frame can be
            # touched by future feeds.
            self._store.evict({0, self._store.n - 1})
        return self

    def _fold_chunk(self, chunk) -> List[RegElement]:
        """Function A on one device: the chunk's pairs, with the pair that
        spans the previous chunk's boundary, as one vmapped cohort; then
        the chunk's frames join the store."""
        prev_last = self._store.last()
        refs = (
            chunk[:-1] if prev_last is None
            else jnp.concatenate([prev_last[None], chunk[:-1]], axis=0)
        )
        tmps = chunk if prev_last is not None else chunk[1:]
        new_elems: List[RegElement] = []
        lanes = int(refs.shape[0])
        if lanes:
            pre_before = self._timings["preprocess"]
            with self._stage("preprocess", "fn_a", lanes=lanes):
                new_elems = self._register_pairs(refs, tmps)
            self._pre_pairs += lanes
            self._pre_seconds += self._timings["preprocess"] - pre_before
        self._store.append_chunk(chunk)
        return new_elems

    def _fold_chunk_chips(self, chunk) -> List[RegElement]:
        """Function A across the session's chips.

        The chunk is split by frame, ``per`` consecutive frames to a chip
        (the last chip's tail padded with copies of the chunk's last
        frame), and each chip registers the pairs whose later frame it
        holds.  A chip's first pair needs the frame before its first: the
        last frame of the chip before, or for chip 0 the previous chunk's
        last frame, copied in as a *halo* (a series' first chunk has no
        pair at position 0; chip 0 registers its first frame against
        itself there, and the result is dropped).  Frame 0 is copied to
        every chip once per session, for function B.  The frames join the
        store on their chips before function A runs.
        """
        chips, store = self._chips, self._store
        d, n, base = len(chips), int(chunk.shape[0]), store.n
        per = -(-n // d)
        frame_bytes = int(chunk.nbytes) // n
        laid = (n == per * d and isinstance(chunk, jax.Array)
                and chunk.sharding.is_equivalent_to(self._chip_sharding,
                                                    chunk.ndim))
        # Halo of chip c: a global frame index, or None where the chip's
        # own first frame stands in (no predecessor, or only padding).
        halo_of = [base - 1 if base else None] + [
            base + c * per - 1 if c * per < n else None for c in range(1, d)
        ]
        copies = sum(1 for c, g in enumerate(halo_of) if g is not None and (
            c > 0 or not store.resident(g, chips[0])))
        copies += (d - 1 if base == 0 else
                   sum(not store.resident(0, dev) for dev in chips))
        nbytes = (0 if laid else per * d * frame_bytes) + copies * frame_bytes
        with self._stage("ingest", "frames.place", bytes=nbytes):
            if not laid:
                if per * d > n:
                    pad = jnp.broadcast_to(chunk[-1:],
                                           (per * d - n,) + chunk.shape[1:])
                    chunk = jnp.concatenate([chunk, pad], axis=0)
                chunk = jax.device_put(chunk, self._chip_sharding)
            rows = _shard_rows(chunk)
            store.append_frames([rows[p // per][p % per] for p in range(n)])
            for c, g in enumerate(halo_of):
                if g is not None:
                    store.place(g, chips[c])
            for dev in chips:
                store.place(0, dev)
            halo = jax.make_array_from_single_device_arrays(
                (d,) + tuple(chunk.shape[1:]), self._chip_sharding,
                [(rows[c][0] if g is None else store.on(g, chips[c])[0])[None]
                 for c, g in enumerate(halo_of)],
            )
            jax.block_until_ready(halo)
        self._placed_bytes += nbytes
        first = 1 if base == 0 else 0
        lanes = n - first
        if not lanes:
            return []
        pre_before = self._timings["preprocess"]
        with self._stage("preprocess", "fn_a", lanes=lanes):
            new_elems = self._register_pairs_chips(halo, chunk, base, n, per)
        self._pre_pairs += lanes
        self._pre_seconds += self._timings["preprocess"] - pre_before
        return new_elems

    def _register_pairs_chips(self, halo, chunk, base: int, n: int,
                              per: int) -> List[RegElement]:
        """The sharded function-A program on a laid-out chunk: elements
        ``(base + p - 1, base + p)`` on the chip of frame ``p``.  Records
        iteration counts and, from the same fetch, one ``repro.fn_a.shard``
        counter per chip and the ``repro.fn_a.lanes`` counter."""
        reg_cfg = self.cfg.registration
        hw = tuple(chunk.shape[1:])
        prog = get_compile_cache().get_compiled(
            ("pair_shards", register_pair, int(chunk.shape[0]), hw,
             str(chunk.dtype), reg_cfg, self._chips),
            lambda: _fn_a_shards(self._chip_sharding.mesh, reg_cfg),
            lower_args=(halo, chunk),
            counters=self._compile,
        )
        res = prog(halo, chunk)
        jax.block_until_ready(res.deformation)
        chip_defs = [dict(zip(res.deformation, leaves)) for leaves in zip(
            *(_shard_rows(a) for a in res.deformation.values()))]
        first = 1 if base == 0 else 0
        new_elems = [
            RegElement(
                jax.tree.map(lambda a, j=p % per: a[j], chip_defs[p // per]),
                base + p - 1, base + p,
            )
            for p in range(first, n)
        ]
        cohorts = [(c * per, (c + 1) * per) for c in range(len(self._chips))]
        self._note_pair_work(res, hw, first, n, cohorts)
        for c, (lo, hi) in enumerate(cohorts):
            with spans.span("fn_a.shard", chip=c,
                            pairs=max(0, min(hi, n) - max(lo, first))):
                pass
        return new_elems

    def _register_pairs(self, refs, tmps) -> List[RegElement]:
        """Function A on the batch of pairs ``(refs[i], tmps[i])``: the new
        scan elements.  Records the pairs' iteration counts and, from the
        same fetch, the ``repro.fn_a.lanes`` counter."""
        reg_cfg = self.cfg.registration
        # AOT-compiled per (pair fn, batch, frame shape, dtype, config)
        # signature: one compile per signature per process, shared across
        # feeds and sessions.  The live module-level ``register_pair`` is
        # part of the key so a swapped implementation never reuses a stale
        # executable.
        pair_fn = get_compile_cache().get_compiled(
            ("pair_vmap", register_pair, int(refs.shape[0]),
             tuple(refs.shape[1:]), str(refs.dtype), reg_cfg),
            lambda: jax.vmap(lambda r, t: register_pair(r, t, None, reg_cfg)),
            lower_args=(refs, tmps),
            counters=self._compile,
        )
        res = pair_fn(refs, tmps)
        jax.block_until_ready(res.deformation)
        first = self._store.n - 1 if self._store.n else 0
        lanes = int(refs.shape[0])
        new_elems = [
            RegElement(
                jax.tree.map(lambda a, i=i: a[i], res.deformation),
                first + i, first + i + 1,
            )
            for i in range(lanes)
        ]
        self._note_pair_work(res, tuple(refs.shape[1:]), 0, lanes,
                             [(0, lanes)])
        return new_elems

    def _note_pair_work(self, res, hw, first: int, n: int, cohorts) -> None:
        """Function A's iteration counts for the batch's pairs ``first``
        to ``n - 1``, and the ``repro.fn_a.lanes`` counter over its
        ``cohorts``: the ``(lo, hi)`` slices of the batch that each run
        one vmapped loop (the whole batch on one device, a chip's block
        across chips)."""
        iters, level_iters = jax.device_get(
            (res.iterations, res.level_iterations)
        )
        self._pair_iters.extend(int(v) for v in iters[first:n])
        if level_iters is None:
            return
        useful = issued = 0
        for lo, hi in cohorts:
            u, i = lane_work(level_iters[lo:hi], hw)
            useful, issued = useful + u, issued + i
        with spans.span("fn_a.lanes", lanes=n - first, useful=useful,
                        issued=issued):
            pass

    @contextlib.contextmanager
    def _stage(self, stage: str, name: str, **stats) -> Iterator[None]:
        """Time one pipeline stage into ``_timings[stage]`` and span the
        same interval as ``repro.<name>``, so the two cannot drift.

        Compile seconds the session's executable cache counts meanwhile go
        to the ``compile`` stage: they used to inflate ``preprocess`` and
        the telemetry prime derived from it (sec/pair), so the dispatcher
        planned the first suffix scan around a compile-dominated cost.
        """
        compile_before = self._compile["compile_s"]
        t0 = time.perf_counter()
        try:
            with spans.span(name, self.id, **stats):
                yield
        finally:
            dt = time.perf_counter() - t0
            dt_compile = self._compile["compile_s"] - compile_before
            self._timings["compile"] += dt_compile
            self._timings[stage] += dt - dt_compile

    def _scan_suffix(self, new_elems: List[RegElement]) -> None:
        cfg = self.cfg
        scan_before = self._timings["scan"]
        seed = self._elements[-1] if self._elements else None
        first_elem = len(self._elements)
        # Compile-classified applications still *happened* this feed — the
        # summary counts work, the EMA alone excludes compile time.
        ops_before = self.telemetry.calls + self.telemetry.compile_calls
        with self._stage("scan", "scan", elements=len(new_elems)):
            if not cfg.refine:
                out = self._compose_suffix(new_elems, seed)
                backend_used = cfg.backend or "vector"
            else:
                out, backend_used = self._refine_suffix(new_elems, seed)
            self._backend_used = backend_used
            self._elements.extend(out)
        self._summaries.append(_ChunkSummary(
            first_elem=first_elem,
            n_elems=len(new_elems),
            seconds=self._timings["scan"] - scan_before,
            ops=self.telemetry.calls + self.telemetry.compile_calls
                - ops_before,
        ))

    def _gathered(self, tree):
        """``tree`` on the session's first chip, where deformations that
        several chips computed are stacked (as is on one device)."""
        return jax.device_put(tree, self._chips[0]) if self._chips else tree

    def _compose_suffix(self, new_elems, seed) -> List[RegElement]:
        """refine=False: exactly-associative pure composition, vectorized —
        one batched engine scan over the chunk, one broadcast seed fold."""
        cfg = self.cfg
        batched = jax.tree.map(
            lambda *ts: jnp.stack(ts, axis=0),
            *self._gathered([e.deformation for e in new_elems]),
        )
        scanned = engine_scan(
            compose_batched,
            batched,
            backend=cfg.backend,
            algorithm=cfg.algorithm,
            workers=cfg.workers,
            devices=self._devices,
            mesh=self._mesh,
        )
        if seed is not None:
            sd = self._gathered(seed.deformation)
            scanned = jax.vmap(lambda d: compose(sd, d))(scanned)
        jax.block_until_ready(scanned)
        base_k = len(self._elements) + 1
        return [
            RegElement(
                jax.tree.map(lambda t, i=i: t[i], scanned), 0, base_k + i
            )
            for i in range(len(new_elems))
        ]

    def _refine_suffix(self, new_elems, seed):
        """refine=True: function-B scan of the suffix, seeded with the
        cumulative element, dispatched with pool awareness."""
        cfg = self.cfg
        registrar = SeriesRegistrar(self._store, cfg.registration, refine=True)
        op = RegistrationOperator(
            registrar,
            name=cfg.telemetry_name,
            telemetry=self.telemetry,
            skip_tol=cfg.skip_tol,
            fused=cfg.fused_ncc,
            session=self.id,
        )
        sec_per_pair = self._pre_seconds / max(self._pre_pairs, 1)
        if op.op_cost_estimate is None and sec_per_pair > 0:
            # Telemetry priming: function A's per-pair cost is the best
            # prior for function B (same minimiser, same frames).
            op.prime(sec_per_pair)
        n_new = len(new_elems)
        if n_new and len(self._pair_iters) >= n_new:
            # The new pairs' function-A iteration counts seed per-element
            # cost priors for this suffix's ahead-of-time segment sizing.
            op.prime_elements(self._pair_iters[-n_new:])
        backend_used = cfg.backend
        algorithm = cfg.algorithm
        num_segments, num_threads = cfg.num_segments, cfg.num_threads
        cross_steal = cfg.cross_steal
        with self.pool.tenant():
            if backend_used is None:
                workers = pool_aware_workers(self.pool, cfg.workers)
                concurrency = op_concurrency_from(op)
                with spans.span("scan.dispatch"):
                    d = cost_dispatch(
                        n_new, domain="element",
                        op_cost=op.op_cost_estimate,
                        workers=workers,
                        op_imbalance=op.op_imbalance_estimate,
                        pool_occupancy=self.pool.occupancy(),
                        op_batchable=op_batchable_from(op),
                        devices=self._devices,
                        op_concurrency=concurrency,
                    )
                with spans.span("scan.plan", backend=d.backend,
                                workers=worker_budget(workers, concurrency),
                                elements=n_new,
                                chips=len(self._chips) if self._chips else 1):
                    pass
                # Execute exactly what the dispatcher decided (its circuit,
                # segment and thread counts — unless the config pins them).
                self._dispatch = d
                backend_used = d.backend
                if algorithm is None:
                    algorithm = d.algorithm
                if num_segments is None:
                    num_segments = d.num_segments
                if num_threads is None:
                    num_threads = d.num_threads
                if cross_steal is None:
                    cross_steal = d.cross_steal
            out = engine_scan(
                op,
                list(new_elems),
                backend=backend_used,
                algorithm=algorithm,
                num_segments=num_segments,
                num_threads=num_threads,
                stealing=cfg.stealing,
                cross_steal=cross_steal,
                workers=cfg.workers,
                seed=seed,
                pool=self.pool,
                devices=self._devices,
                mesh=self._mesh,
            )
        if backend_used == "hierarchical":
            from repro.core.engine import hierarchical

            self._scan_stats = hierarchical.last_stats
            self._steals += self._scan_stats.total_inter_segment_steals()
        elif backend_used == "sharded":
            from repro.core.engine import sharded

            self._scan_stats = sharded.last_stats
        return out, backend_used

    # -------------------------------------------------------------- result

    def result(self) -> SeriesResult:
        """Assemble the :class:`SeriesResult` for everything fed so far.

        Does *not* finalize the session: ``feed``/``extend`` keep working
        afterwards (a frame arriving after completion folds in at O(new)).

        Blocking, but cheap relative to ``feed`` — it only stacks the
        retained per-element deformations (the ``compose`` timing stage);
        no operator applications happen here.  The returned object is a
        snapshot: safe to hand to other threads.
        """
        self._check_open()
        if not self._elements:
            raise ValueError(
                f"register_series needs >= 2 frames, got {self._store.n}"
            )
        with self._stage("compose", "result"):
            all_defs = self._gathered([identity_deformation()] + [
                e.deformation for e in self._elements
            ])
            deformations = jax.tree.map(
                lambda *ts: jnp.stack([jnp.asarray(t) for t in ts], axis=0),
                *all_defs,
            )
            jax.block_until_ready(deformations)
        return SeriesResult(
            deformations=deformations,
            elements=list(self._elements),
            timings=dict(self._timings),
            backend=self._backend_used or "none",
            op_telemetry=self.telemetry.summary(),
            scan_stats=self._scan_stats,
            dispatch=self._dispatch,
            compile_cache=dict(self._compile),
        )

    def extend(self, new_frames) -> SeriesResult:
        """Fold a suffix of frames in and return the updated result.

        O(new) operator applications + an O(log S) cross-segment phase —
        never a recompute of the existing prefix.  Valid before or after
        ``result()``.
        """
        self.feed(new_frames)
        return self.result()

    # ------------------------------------------------------------ recovery

    def checkpoint(self) -> int:
        """Snapshot the scan state; returns the step (frames seen).

        The snapshot holds the cumulative deformations, the two resident
        boundary frames, the per-pair cost history and the telemetry
        prime — everything ``restore`` needs to continue the series.
        """
        self._check_open()
        if self._ckpt is None:
            raise ValueError(
                "session was opened without checkpoint_dir; pass one to "
                "open_series(..., checkpoint_dir=...)"
            )
        if not self._elements:
            raise ValueError("nothing to checkpoint: no elements scanned yet")
        m = self._store.n
        cum = jax.tree.map(
            lambda *ts: jnp.stack([jnp.asarray(t) for t in ts], axis=0),
            *self._gathered([e.deformation for e in self._elements]),
        )
        state = {
            "cum": cum,
            "frame0": self._store[0],
            "last_frame": self._store[m - 1],
            "pair_iters": jnp.asarray(self._pair_iters, jnp.int32),
        }
        meta = {
            "session_id": self.id,
            "n_frames": m,
            "backend": self._backend_used,
            "cfg": dataclasses.asdict(self.cfg),
            "telemetry_name": self.cfg.telemetry_name,
            "telemetry_ema_s": self.telemetry.summary()["ema_s"],
            "timings": dict(self._timings),
            "pre_seconds": self._pre_seconds,
            "pre_pairs": self._pre_pairs,
            "summaries": [dataclasses.asdict(s) for s in self._summaries],
        }
        self._ckpt.save(m, state, meta)
        self._ckpt.wait()
        return m

    @classmethod
    def restore(
        cls,
        checkpoint_dir: str,
        cfg: Optional[RegisterSeriesConfig] = None,
        *,
        pool=None,
        step: Optional[int] = None,
    ) -> "SeriesSession":
        """Rebuild a mid-series session from its latest (or given) snapshot.

        The restored session resumes exactly where the snapshot left off:
        retained cumulative elements, boundary frames, cost history and a
        re-primed telemetry EMA (per-call imbalance statistics restart
        from scratch, so cross-segment stealing re-enters its unobserved
        insurance mode until new samples arrive).

        ``cfg=None`` rebuilds the config the snapshot was taken under
        (the default — the suffix continues under the same minimiser
        settings as the prefix); an explicit ``cfg`` must agree on the
        registration-affecting fields (``registration``/``refine``) or
        restore refuses, since a mixed-settings series is silent data
        corruption.
        """
        ckpt = Checkpointer(checkpoint_dir, async_save=False)
        by_key, meta, _step = ckpt.restore_raw(step=step)
        saved_cfg = meta.get("cfg")
        if saved_cfg is not None:
            stored = RegisterSeriesConfig(
                registration=RegistrationConfig(**saved_cfg["registration"]),
                **{k: v for k, v in saved_cfg.items() if k != "registration"},
            )
            if cfg is None:
                cfg = stored
            elif (cfg.registration, cfg.refine) != (
                stored.registration, stored.refine,
            ):
                raise ValueError(
                    "restore cfg disagrees with the snapshot's "
                    "registration-affecting settings "
                    f"(snapshot: registration={stored.registration}, "
                    f"refine={stored.refine}); resume with cfg=None or "
                    "matching settings"
                )
        self = cls(
            cfg,
            pool=pool,
            session_id=meta["session_id"],
            checkpoint_dir=checkpoint_dir,
        )
        m = int(meta["n_frames"])
        # Rebuild the deformation pytree generically from the flattened
        # checkpoint keys — the schema belongs to the Deformation type,
        # not to this method (a variant with extra leaves must round-trip).
        cum = _unflatten_keys({
            k[len("cum/"):]: jnp.asarray(v)
            for k, v in by_key.items() if k.startswith("cum/")
        })
        self._elements = [
            RegElement(jax.tree.map(lambda t, i=i: t[i], cum), 0, i + 1)
            for i in range(m - 1)
        ]
        self._store.restore(m, {
            0: jnp.asarray(by_key["frame0"]),
            m - 1: jnp.asarray(by_key["last_frame"]),
        })
        self._pair_iters = [int(v) for v in by_key["pair_iters"]]
        self._backend_used = meta.get("backend")
        self._timings.update(meta.get("timings", {}))
        self._pre_seconds = float(meta.get("pre_seconds", 0.0))
        self._pre_pairs = int(meta.get("pre_pairs", 0))
        self._summaries = [
            _ChunkSummary(**s) for s in meta.get("summaries", [])
        ]
        ema = meta.get("telemetry_ema_s") or 0.0
        if ema > 0:
            self.telemetry.record(float(ema))
        return self

    # ------------------------------------------------------------ lifetime

    def close(self) -> None:
        """Release the session's telemetry channel and frame window."""
        if self._closed:
            return
        self._closed = True
        release_telemetry(self.cfg.telemetry_name, session=self.id)
        self._store = _FrameStore()

    def __enter__(self) -> "SeriesSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def open_series(
    cfg: Optional[RegisterSeriesConfig] = None,
    *,
    pool=None,
    session_id: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
    compile_cache_dir: Optional[str] = None,
) -> SeriesSession:
    """Open a resident series session on the shared runtime.

    ``pool``: the :class:`~repro.runtime.scheduler.WorkerPool` to execute
    on (process-wide shared pool by default).  ``checkpoint_dir`` enables
    ``session.checkpoint()`` / :meth:`SeriesSession.restore`.
    ``compile_cache_dir`` points the persistent compilation cache (lowered
    plans, and XLA executables unless ``$JAX_COMPILATION_CACHE_DIR`` is
    set) at a directory so restarts warm-start
    (:mod:`repro.runtime.compile_cache`).
    """
    return SeriesSession(
        cfg, pool=pool, session_id=session_id, checkpoint_dir=checkpoint_dir,
        compile_cache_dir=compile_cache_dir,
    )
