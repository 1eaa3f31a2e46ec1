"""Sharded (partitioned) parameter server — per-shard locks and gating.

The monolithic ``ParameterServer`` serializes *every* push on one lock
and one version counter: the exact single-machine bottleneck the PS
framework exists to avoid.  Here the weight pytree is partitioned by a
``ShardPlan`` into S size-balanced shards, and every shard owns its own

  * lock (condition variable)      — pushes to distinct shards overlap,
  * version counter                — per-shard applied-update count,
  * ``ServerOptimizer`` state      — momentum lives with its slice,
  * ``SyncPolicy`` + ``StalenessTracker`` — per-shard Algorithm-1 gating,
  * ``RunMetrics``                 — per-shard staleness/wait accounting.

Gating modes
------------
``sharded`` (default)  every shard gates independently with its own
    policy instance; a DSSP shard's Algorithm-2 controller reads that
    shard's interval table (table A), so skewed shard load produces
    per-shard credit schedules.  A worker's push returns when the LAST
    shard releases it.
``global``  one policy/tracker gates the worker exactly once per push
    (the monolithic semantics) while the weight store stays partitioned —
    the ablation that isolates lock-granularity wins from gating wins.

Wire compression (``optim/compression.py``) runs per shard with
per-(worker, shard) error-feedback state, emulating worker-side
compression of each shard RPC.

The apply path is pluggable: ``apply_mode='tree'`` steps the shard's
piece list through its ``ServerOptimizer`` (bitwise-identical to the
monolithic server), ``apply_mode='fused'`` keeps params+momentum packed
in one lane-aligned (rows, 512) buffer and folds the whole shard through
a single Pallas ``fused_update`` launch per push.

Packed wire format (the zero-repack hot path)
---------------------------------------------
``push``/``pull`` speak the *tree* wire format: per-leaf arrays, split
and reassembled on every hop.  ``push_packed``/``pull_packed`` speak the
plan's packed wire format instead — the worker packs its gradients once
(inside its jitted step) and every later hop is layout-preserving:

  * ``push_packed`` slices the incoming wire buffer into per-shard
    row-range *views* (``ShardPlan.shard_wire``) — zero host-side
    per-leaf concatenations on the server, asserted by the
    ``repro.perfcount`` probes,
  * each shard folds its region straight through ONE ``fused_update``
    launch (no ``pack_shard`` per push), plus at most one fused
    compression launch (``wire_compression=``) with per-(worker, shard)
    error-feedback buffers kept in wire layout,
  * ``pull_packed`` serves a version-keyed packed snapshot: per-shard
    buffers are reference-grabbed under their own locks, the full wire
    buffer is concatenated OUTSIDE any lock and cached until some shard
    version moves.

Tree-format ``pull`` in fused mode also rebuilds its per-shard piece
cache outside the shard lock, so a pull after an apply never stalls
concurrent pushes to that shard while it unpacks.

Coalesced apply + version-delta pulls (work ∝ rounds + change)
--------------------------------------------------------------
With W workers the paths above still do O(W) kernel launches per round
per shard and ship the full snapshot on every pull.  Two knobs make
server work scale with *rounds and changed state* instead:

  * ``coalesce=K`` arms a bounded micro-batching window per shard:
    contributions that arrive while a flush is in flight (or within a
    short linger, ``coalesce_wait``) are drained together through ONE
    ``fused_update_batched`` launch — an in-kernel sequential fold, so
    numerics match the uncoalesced path (bitwise for f32 state and for
    any window of one) while launches per round drop from S x W toward
    S.  The sync policy still sees, decides and releases every
    contributing worker individually: BSP/SSP/DSSP semantics are
    untouched.
  * ``pull_delta(worker, versions)`` returns only the shards whose
    version moved past the worker's last-seen vector (full-snapshot
    fallback on a vector mismatch), so steady-state pull bytes are
    proportional to what actually changed.

Live reshard (S -> S')
----------------------
``reshard(n_shards)`` migrates the packed parameter+momentum regions
into a new plan WITHOUT stopping training (protocol + migration map in
``repro.ft.reshard``).  Old shards are retired one at a time under
their own locks (the only per-shard pause, traced as
``reshard_shard``); pushes that land on a retired shard PARK their
packed region and are replayed through the migration map after the
atomic ``(plan, shards, n_shards)`` swap — applied exactly once,
accounted in ``WIRE.reshard_parked``/``reshard_replayed``.  Each swap
bumps ``reshard_epoch``; stale-epoch pushes (clients that packed
against the old layout) are translated through the retained migration
maps, and delta pulls carry the epoch so clients force the full-pull
fallback and rebuild.
"""

from __future__ import annotations

import collections.abc
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro._compat import warn_legacy
from repro.api.protocol import DeltaPull, ParameterServerProtocol
from repro.core.policies import Decision, SyncPolicy
from repro.core.staleness import StalenessTracker
from repro.obs.trace import TRACE
from repro.optim.compression import Compressor
from repro.perfcount import WIRE
from repro.ps.metrics import RunMetrics
from repro.ps.server import (DEFAULT_COALESCE_WAIT_S, CoalesceWindow,
                             ServerOptimizer)
from repro.ps.sharded.plan import ShardPlan, build_shard_plan
from repro.wireformat import WIRE_LANES

Params = Any
Grads = Any


@jax.jit
def wire_concat(*bufs: jax.Array) -> jax.Array:
    """The shard buffers as one wire, in one program named for its
    device phase (``repro.obs.trace.PHASE_PROGRAMS``)."""
    return jnp.concatenate(bufs)


@functools.partial(jax.jit, static_argnames=("rows",))
def apply_region(wire: jax.Array, start, rows: int) -> jax.Array:
    """Rows ``[start, start + rows)`` of a packed wire: one shard's
    gradient region, cut in a program of the ``apply`` phase."""
    return jax.lax.dynamic_slice_in_dim(wire, start, rows)


class _ShardRegions(collections.abc.Sequence):
    """The shard regions of one packed wire, each sliced when a shard
    reads it.  Slicing all S up front would hold a second copy of the
    whole gradient on the device for the length of the push."""

    def __init__(self, plan: ShardPlan, wire: jax.Array):
        self._plan, self._wire = plan, wire

    def __len__(self) -> int:
        return self._plan.n_shards

    def __getitem__(self, j: int) -> jax.Array:
        if not 0 <= j < self._plan.n_shards:
            raise IndexError(j)
        layout = self._plan.wire_layout()
        return apply_region(self._wire, layout.shard_row_start[j],
                            layout.shard_rows[j])


class _ShardState:
    """Everything one shard owns.  All mutation under ``self.cond``."""

    def __init__(self, index: int, plan: ShardPlan,
                 pieces: List[jax.Array],
                 policy: SyncPolicy, optimizer: ServerOptimizer,
                 workers: Sequence[int], apply_mode: str):
        self.index = index
        self.plan = plan
        self.cond = threading.Condition()
        self.policy = policy
        self.optimizer = optimizer
        self.tracker = StalenessTracker(workers)
        self.metrics = RunMetrics(policy=f"{policy.name}/shard{index}",
                                  n_workers=len(list(workers)))
        self.version = 0
        self.apply_mode = apply_mode
        #: set by the server when coalescing is armed (fused mode):
        #: the shard's ``CoalesceWindow`` over its packed buffers.
        self.window = None
        #: live-reshard state (see ``ShardedParameterServer.reshard``):
        #: a retired shard parks incoming applies for replay; an
        #: abandoned shard releases its barrier waiters (its peers now
        #: push to the new shards).
        self.retired = False
        self.abandoned = False
        self.parked: List[Any] = []   # (packed region, staleness) pairs
        if apply_mode == "fused":
            # Params + momentum stay resident in the plan's wire layout
            # (8-row-aligned (rows, 512) region), so an incoming packed
            # push folds in directly with zero re-packing; unpacked
            # pieces are a cache rebuilt at most once per version —
            # OUTSIDE the shard lock (see ``_shard_snapshot``).
            self._packed_p = plan.pack_shard_pieces(pieces, index)
            self._packed_m = jnp.zeros_like(self._packed_p)
            self._pieces: Optional[List[jax.Array]] = list(pieces)
        else:
            self._pieces = list(pieces)

    @classmethod
    def from_packed(cls, index: int, plan: ShardPlan,
                    packed_p: jax.Array, packed_m: jax.Array, version: int,
                    policy: SyncPolicy, optimizer: ServerOptimizer,
                    workers: Sequence[int]) -> "_ShardState":
        """A shard state born from migrated packed buffers (fused mode
        only): what a live reshard installs — params AND momentum carry
        over bitwise, the version is the redistributed share of the old
        sum."""
        st = cls.__new__(cls)
        st.index = index
        st.plan = plan
        st.cond = threading.Condition()
        st.policy = policy
        st.optimizer = optimizer
        st.tracker = StalenessTracker(workers)
        st.metrics = RunMetrics(policy=f"{policy.name}/shard{index}",
                                n_workers=len(list(workers)))
        st.version = int(version)
        st.apply_mode = "fused"
        st.window = None
        st.retired = False
        st.abandoned = False
        st.parked = []
        st._packed_p = packed_p
        st._packed_m = packed_m
        st._pieces = None
        return st

    # -- weight access (call under self.cond) -------------------------------
    def pieces(self) -> List[jax.Array]:
        if self._pieces is None:  # fused mode, invalidated by an apply
            self._pieces = self.plan.shard_pieces_from_wire(
                self._packed_p, self.index)
        return self._pieces

    def apply(self, grad_pieces: List[jax.Array], staleness: int) -> None:
        """Tree-wire apply: one piece list, optimizer step or pack+fold."""
        if not grad_pieces:
            # Empty shard (more shards than pieces): the gate/version
            # bookkeeping stays uniform, there is just nothing to fold in
            # (a zero-row pallas_call would reject its (8, 512) tile).
            self.version += 1
            return
        if self.apply_mode == "fused":
            self.apply_packed(
                self.plan.pack_shard_pieces(grad_pieces, self.index),
                staleness)
        else:
            self._pieces = self.optimizer.step(self.pieces(), grad_pieces,
                                               staleness)
            self.version += 1

    def apply_packed(self, wire_g: jax.Array, staleness: int) -> None:
        """Packed-wire apply: fold the shard's (rows, 512) gradient region
        straight through one ``fused_update`` launch — no per-leaf work.
        Fused mode only (``push_packed`` guards at the server boundary)."""
        if wire_g.shape[0] == 0:      # empty shard
            self.version += 1
            return
        # Kernel imports stay local to the fused path so plain
        # `import repro.ps` never pulls in the Pallas kernel stack.
        from repro.kernels import ops as kops
        opt = self.optimizer
        scale = (1.0 / (1.0 + staleness)
                 if opt.staleness_damping else 1.0)
        self._packed_p, self._packed_m = kops.fused_update(
            self._packed_p, self._packed_m, wire_g,
            lr=opt.lr, beta=opt.momentum, scale=scale)
        self._pieces = None
        self.version += 1


class ShardedParameterServer(ParameterServerProtocol):
    """Partitioned weight store + per-shard Algorithm-1 gating.

    Implements ``repro.api.protocol.ParameterServerProtocol`` — the
    same surface as the monolithic ``ParameterServer`` (plus the
    overridden per-shard variants), so workers, endpoints and sessions
    drive either server without a type branch.
    """

    def __init__(self, params: Params, policy_factory: Callable[[], SyncPolicy],
                 optimizer_factory: Callable[[], ServerOptimizer],
                 n_workers: int, n_shards: int, *,
                 split_oversized: bool = True,
                 gating: str = "sharded",
                 apply_mode: str = "tree",
                 compressor: Optional[Compressor] = None,
                 wire_compression: Optional[str] = None,
                 topk_fraction: float = 0.05,
                 coalesce: int = 1,
                 coalesce_wait: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        warn_legacy("ShardedParameterServer",
                    "repro.api.build_session(RunSpec(ps=ServerSpec("
                    "kind='sharded', ...)))")
        if gating not in ("sharded", "global"):
            raise ValueError(f"unknown gating mode {gating!r}")
        if apply_mode not in ("tree", "fused"):
            raise ValueError(f"unknown apply mode {apply_mode!r}")
        if wire_compression not in (None, "none", "", "int8", "topk"):
            raise ValueError(
                f"unknown wire compression {wire_compression!r}")
        if coalesce < 1:
            raise ValueError(f"coalesce window must be >= 1, got {coalesce}")
        if coalesce > 1 and apply_mode != "fused":
            raise ValueError("coalesce > 1 batches packed applies; it "
                             "requires apply_mode='fused'")
        self.coalesce = coalesce
        self.coalesce_wait = (coalesce_wait if coalesce_wait is not None
                              else (DEFAULT_COALESCE_WAIT_S
                                    if coalesce > 1 else 0.0))
        self.plan: ShardPlan = build_shard_plan(
            params, n_shards, split_oversized=split_oversized)
        self.gating = gating
        self.n_shards = n_shards
        self.apply_mode = apply_mode
        self._split_oversized = split_oversized
        # Factories are kept so a live reshard can mint policies and
        # optimizer state for the new shard set.
        self._policy_factory = policy_factory
        self._optimizer_factory = optimizer_factory
        # Live-reshard state: the epoch counts completed migrations and
        # rides HELLO/SUB/DELTA replies; ``_reshard_cond`` makes
        # ``(plan, shards, n_shards, epoch)`` reads/swaps atomic and
        # tracks in-flight pushes per epoch so parked regions are
        # replayed only once nothing can still append to them.
        self.reshard_epoch = 0
        self._reshard_lock = threading.Lock()     # one migration at a time
        self._reshard_cond = threading.Condition()
        self._inflight: Dict[int, int] = {}       # epoch -> active pushes
        self._retired_plans: Dict[int, ShardPlan] = {}
        self._migrations: Dict[int, Any] = {}     # epoch e -> map e -> e+1
        workers = range(n_workers)
        pieces = self.plan.split(params)
        self.shards: List[_ShardState] = [
            _ShardState(j, self.plan, pieces[j], policy_factory(),
                        optimizer_factory(), workers, apply_mode)
            for j in range(n_shards)]
        if apply_mode == "fused":
            for st in self.shards:
                st.window = self._make_window(st)
        if gating == "global":
            self._gate_policy = policy_factory()
            self._gate_tracker = StalenessTracker(workers)
            self._gate_cond = threading.Condition()
        self.metrics = RunMetrics(
            policy=f"{self.shards[0].policy.name} xS{n_shards}[{gating}]",
            n_workers=n_workers)
        self._metrics_lock = threading.Lock()
        self.compressor = (compressor
                           if compressor is not None
                           and compressor.name != "none" else None)
        self._err: Dict[int, List[Any]] = {}   # worker -> per-shard err state
        # Packed-path fused wire compression: per-(worker, shard) f32
        # error-feedback buffers, kept in wire layout.
        from repro.optim.compression import make_packed_compressor
        self.wire_compression = make_packed_compressor(
            wire_compression, fraction=topk_fraction)
        self._wire_err: Dict[int, Dict[int, jax.Array]] = {}
        # Version-keyed packed snapshot cache for ``pull_packed``.
        self._snap_lock = threading.Lock()
        self._snap_key: Optional[tuple] = None
        self._snap_wire: Optional[jax.Array] = None
        self._clock = clock
        self._t0 = clock()
        self.stopped = False

    # -- worker API ----------------------------------------------------------
    def _plan_state(self):
        """Mutually-consistent ``(plan, shards, epoch)``: a live reshard
        swaps all three under ``_reshard_cond``, so readers that touch
        more than one must grab them together."""
        with self._reshard_cond:
            return self.plan, self.shards, self.reshard_epoch

    def _plan_for_epoch(self, epoch: Optional[int]):
        """The plan a push was packed against.  ``None`` / the current
        epoch -> the live plan; an older epoch -> the retired plan kept
        for stale-push translation (raises once evicted — the client
        must re-pull, a retryable condition)."""
        with self._reshard_cond:
            cur = self.reshard_epoch
            if epoch is None or int(epoch) == cur:
                return self.plan, cur
            plan = self._retired_plans.get(int(epoch))
        if plan is None:
            raise ValueError(
                f"unknown reshard epoch {epoch} (server at {cur}); "
                "re-pull to resync")
        return plan, int(epoch)

    def _shard_snapshot(self, st: _ShardState) -> List[jax.Array]:
        """One shard's piece list, unpacking OUTSIDE the shard lock.

        In fused mode an apply invalidates the piece cache; rebuilding it
        while holding ``st.cond`` would stall every concurrent push to
        that shard for the full unpack.  Instead: grab the (immutable)
        packed buffer + version under the lock, unpack unlocked, and
        install the cache only if the shard has not moved meanwhile.
        """
        with st.cond:
            if st._pieces is not None:
                return list(st._pieces)
            packed, version = st._packed_p, st.version
        pieces = self.plan.shard_pieces_from_wire(packed, st.index)
        with st.cond:
            if st.version == version and st._pieces is None:
                st._pieces = list(pieces)
        return pieces

    def pull(self, worker: int) -> Params:
        """Reassemble the full pytree from per-shard snapshots.

        Each shard is snapshotted under its OWN lock; shards mutated
        concurrently with the pull may differ in version — exactly the
        per-shard consistency a partitioned PS offers (each shard's slice
        is internally consistent; cross-shard skew is bounded by the
        gating policies).
        """
        t0 = TRACE.now() if TRACE.enabled else 0.0
        plan, shards, _ = self._plan_state()
        params = plan.assemble(
            [self._shard_snapshot(st) for st in shards])
        if TRACE.enabled:
            TRACE.span("pull", t0, worker=worker)
        return params

    def pull_packed(self, worker: int = -1) -> jax.Array:
        """Full (total_rows, 512) wire snapshot of the parameters.

        Per-shard packed buffers are reference-grabbed under their own
        locks (jax arrays are immutable, so a reference IS a snapshot);
        the concatenation into one wire buffer happens OUTSIDE any shard
        lock and is cached keyed by the shard-version vector, so pulls
        between applies are a dictionary hit.
        """
        if self.apply_mode != "fused":
            raise ValueError("pull_packed requires apply_mode='fused' "
                             "(tree mode has no resident packed store)")
        t0 = TRACE.now() if TRACE.enabled else 0.0
        _, shards, epoch = self._plan_state()
        snaps, versions = [], []
        for st in shards:
            with st.cond:
                snaps.append(st._packed_p)
                versions.append(st.version)
        # The cache key leads with the reshard epoch: version vectors
        # from different epochs have different arity and are not
        # comparable — a newer epoch always wins.
        key = (epoch,) + tuple(versions)
        with self._snap_lock:
            if self._snap_key == key:
                wire = self._snap_wire
                if TRACE.enabled:
                    TRACE.span("pull", t0, worker=worker,
                               args={"packed": True, "cached": True})
                return wire
        bufs = [b for b in snaps if b.shape[0]]
        if len(bufs) == 1:
            wire = bufs[0]
        else:
            wire = wire_concat(*bufs)
            WIRE.pull_assemble_bytes += wire.nbytes
        with self._snap_lock:
            # A slower concurrent pull may finish its concat AFTER a
            # fresher one: install only if this snapshot DOMINATES the
            # cached one (component-wise >=, somewhere >).  The old
            # any-newer guard let two pulls that interleaved their
            # per-shard grabs replace a cache entry with one that was
            # OLDER on some shard — never inconsistent (key and wire
            # install as a pair), but non-monotone.  The concurrency
            # regression test hammers push+pull and asserts the cached
            # key always matches the cached bytes and never regresses.
            cached = self._snap_key
            if cached is None or key[0] > cached[0] or (
                    key[0] == cached[0]
                    and all(n >= c for n, c in zip(key[1:], cached[1:]))
                    and any(n > c for n, c in zip(key[1:], cached[1:]))):
                self._snap_key, self._snap_wire = key, wire
        if TRACE.enabled:
            TRACE.span("pull", t0, worker=worker,
                       args={"packed": True, "cached": False})
        return wire

    def pull_packed_shard(self, shard: int, worker: int = -1) -> jax.Array:
        """One shard's resident (rows, 512) region — a reference IS a
        consistent snapshot (jax arrays are immutable).  The per-shard
        granularity the transport endpoints route on."""
        if self.apply_mode != "fused":
            raise ValueError("pull_packed_shard requires apply_mode='fused' "
                             "(tree mode has no resident packed store)")
        _, shards, _ = self._plan_state()
        st = shards[shard]
        with st.cond:
            return st._packed_p

    def pull_delta(self, worker: int,
                   versions: Optional[Sequence[int]]) -> DeltaPull:
        """Version-delta pull: ship only the shards that advanced.

        ``versions`` is the per-shard version vector the worker saw on
        its LAST pull; the reply carries the regions of shards whose
        version moved (each region reference-grabbed with its version
        under that shard's lock — the same per-shard consistency as
        ``pull_packed``) plus the fresh vector.  A vector of the wrong
        arity, or one claiming versions the server has not reached,
        cannot be diffed against — the reply falls back to a full
        snapshot (every non-empty shard, ``full=True``).  Bytes shipped
        and bytes avoided are accounted in ``repro.perfcount.WIRE``.
        """
        if self.apply_mode != "fused":
            raise ValueError("pull_delta requires apply_mode='fused' "
                             "(tree mode has no resident packed store)")
        t0 = TRACE.now() if TRACE.enabled else 0.0
        plan, shards, epoch = self._plan_state()
        n_shards = len(shards)
        snaps, cur = [], []
        for st in shards:
            with st.cond:
                snaps.append(st._packed_p)
                cur.append(st.version)
        cur_t = tuple(cur)
        layout = plan.wire_layout()
        itemsize = jnp.dtype(layout.dtype).itemsize
        full_bytes = layout.total_rows * WIRE_LANES * itemsize
        # An arity mismatch is exactly what a client sees after a live
        # reshard: its vector is from the old epoch and cannot be
        # diffed — the full-snapshot fallback IS the resync.
        mismatch = (versions is None or len(versions) != n_shards
                    or any(int(v) > c for v, c in zip(versions, cur)))
        if mismatch:
            changed = [j for j in range(n_shards)
                       if snaps[j].shape[0]]
        else:
            changed = [j for j, (v, c) in enumerate(zip(versions, cur))
                       if int(v) != c and snaps[j].shape[0]]
        regions = tuple(snaps[j] for j in changed)
        delta_bytes = sum(int(r.shape[0]) for r in regions) \
            * WIRE_LANES * itemsize
        WIRE.delta_bytes_tx += delta_bytes
        if not mismatch:
            WIRE.full_pull_bytes_avoided += full_bytes - delta_bytes
        if TRACE.enabled:
            TRACE.span("pull_delta", t0, worker=worker,
                       args={"shards": len(changed), "bytes": delta_bytes,
                             "full": mismatch})
        return DeltaPull(versions=cur_t, shards=tuple(changed),
                         regions=regions, full=mismatch, epoch=epoch)

    def push_packed_shard(self, worker: int, shard: int, buf) -> None:
        """Single-shard packed push: the unit of per-shard endpoint
        routing (``repro.transport``), where different shards of this
        server live behind different endpoints.

        Gating/apply semantics are the sharded ones — this shard's
        policy gates the worker independently.  ``gating='global'`` is
        rejected: the global gate's decision spans all shards of one
        logical push, which no longer exists once shards are routed to
        different endpoints.

        Accounting is per shard ONLY (``shard_metrics()``): one logical
        gradient routed across S endpoints is S of these calls, and
        folding each into the aggregate ``self.metrics`` would inflate
        ``total_pushes``/staleness S-fold versus the same gradient
        pushed through ``push_packed`` (which records the aggregate
        once, max-staleness folded).
        """
        if self.apply_mode != "fused":
            raise ValueError("push_packed_shard requires apply_mode='fused' "
                             "(tree mode has no resident packed store)")
        if self.gating == "global":
            raise ValueError(
                "per-shard routed pushes require gating='sharded' (the "
                "global gate must see one push spanning all shards)")
        with self._reshard_cond:
            plan, shards, epoch = self.plan, self.shards, self.reshard_epoch
            self._inflight[epoch] = self._inflight.get(epoch, 0) + 1
        try:
            layout = plan.wire_layout()
            if buf.shape != (layout.shard_rows[shard], WIRE_LANES):
                raise ValueError(
                    f"shard {shard}: buffer {buf.shape} does not match "
                    f"layout ({layout.shard_rows[shard]}, {WIRE_LANES})")
            if self.wire_compression is not None:
                buf = self._compress_packed_one(worker, shard, buf)
            self._push_shard(shards[shard], worker, buf, packed=True)
        finally:
            with self._reshard_cond:
                self._inflight[epoch] -= 1
                self._reshard_cond.notify_all()

    def push(self, worker: int, grads: Grads) -> None:
        """Split grads by the plan and push shard-by-shard.

        Every worker visits shards in the SAME canonical order 0..S-1:
        with blocking policies a per-worker rotated order deadlocks
        (worker A blocked at shard 0's barrier while worker B, whose push
        would release it, is blocked at shard 1's — a circular wait).  A
        total order keeps the wait-for graph acyclic while pushes to
        distinct shards still overlap in pipeline fashion.  Blocks until
        every shard's policy has released the worker (the ``global`` mode
        gates once, after all applies).
        """
        plan, _, epoch = self._plan_state()
        pieces_per_shard = plan.split(grads)
        if self.compressor is not None:
            pieces_per_shard = self._compress(worker, pieces_per_shard)
        self._push_payloads(worker, pieces_per_shard, packed=False,
                            epoch=epoch)

    def push_packed(self, worker: int, wire, epoch: Optional[int] = None
                    ) -> None:
        """Packed-wire push: the zero-repack hot path.

        ``wire`` is either the full (total_rows, 512) buffer (the worker
        packed once in its jitted step) or a list of per-shard regions.
        The server only takes row-range VIEWS — no per-leaf concatenate,
        no ``pack_shard`` — and each shard folds its region through one
        ``fused_update`` launch (plus one fused-compression launch when
        ``wire_compression`` is set).  Gating/metrics semantics are
        identical to ``push``.

        ``epoch`` is the reshard epoch the pusher packed against
        (transports carry it on the frame).  A stale epoch means the
        layout changed under the client: the push is validated against
        the RETIRED plan and translated through the migration map, so
        nothing a lagging client sent is lost.  ``None`` means "the
        layout this buffer matches" — inferred for in-heap callers that
        hold a plan reference rather than an epoch.
        """
        if self.apply_mode != "fused":
            raise ValueError("push_packed requires apply_mode='fused' "
                             "(tree mode has no resident packed store)")
        if epoch is None and not isinstance(wire, (list, tuple)):
            epoch = self._infer_epoch(int(wire.shape[0]))
        plan, epoch = self._plan_for_epoch(epoch)
        layout = plan.wire_layout()
        if isinstance(wire, (list, tuple)):
            shard_bufs = list(wire)
            if len(shard_bufs) != plan.n_shards:
                raise ValueError(f"got {len(shard_bufs)} shard buffers, "
                                 f"plan has {plan.n_shards} shards")
            for j, buf in enumerate(shard_bufs):
                if buf.shape != (layout.shard_rows[j], WIRE_LANES):
                    raise ValueError(
                        f"shard {j}: buffer {buf.shape} does not match "
                        f"layout ({layout.shard_rows[j]}, {WIRE_LANES})")
        else:
            # Python slicing clamps, so an undersized buffer would
            # silently hand trailing shards a (0, 512) "empty" region
            # and drop their updates — reject it up front.
            if wire.shape != (layout.total_rows, WIRE_LANES):
                raise ValueError(
                    f"wire buffer {wire.shape} does not match layout "
                    f"({layout.total_rows}, {WIRE_LANES})")
            shard_bufs = _ShardRegions(plan, wire)
        # Stale-epoch pushes skip wire compression: the per-(worker,
        # shard) error-feedback buffers were reset at the swap and are
        # shaped for the NEW plan — a lossless transition-window push
        # beats quantizing against mismatched feedback state.
        if self.wire_compression is not None and epoch == self.reshard_epoch:
            shard_bufs = self._compress_packed(worker, shard_bufs)
        self._push_payloads(worker, shard_bufs, packed=True, epoch=epoch)

    def _infer_epoch(self, rows: int) -> Optional[int]:
        """Map a full-buffer row count onto the epoch whose layout it
        matches — newest first, so in-heap callers still holding an old
        plan keep working across a reshard."""
        with self._reshard_cond:
            if self.plan.wire_layout().total_rows == rows:
                return self.reshard_epoch
            for e in sorted(self._retired_plans, reverse=True):
                if self._retired_plans[e].wire_layout().total_rows == rows:
                    return e
            return self.reshard_epoch   # let validation raise with detail

    def _push_payloads(self, worker: int, payloads: Sequence[Any],
                       packed: bool, epoch: Optional[int] = None) -> None:
        t_push = TRACE.now() if TRACE.enabled else 0.0
        # Atomically: which epoch's shard set does this push apply to,
        # and register it in flight — a live reshard replays parked
        # regions only after every push registered under the old epoch
        # has finished (nothing can still append to a parked list).
        with self._reshard_cond:
            cur = self.reshard_epoch
            shards = self.shards
            self._inflight[cur] = self._inflight.get(cur, 0) + 1
        try:
            if epoch is not None and epoch != cur:
                if not packed:
                    # A tree push that raced the swap: pack each piece
                    # list into its OLD-plan region, then translate like
                    # any other stale packed push.
                    old_plan = self._retired_plans.get(epoch)
                    if old_plan is None:
                        raise ValueError(
                            f"unknown reshard epoch {epoch}; re-pull")
                    payloads = [old_plan.pack_shard_pieces(p, j)
                                for j, p in enumerate(payloads)]
                    packed = True
                payloads = self._translate_stale(payloads, epoch, cur)
            now = self._clock() - self._t0
            # Global mode: the gate decides FIRST (monolithic order —
            # decide, apply, then maybe block), and its decision governs
            # every shard's apply so update-dropping policies (backup
            # workers) and credit accounting match the monolithic server
            # exactly.
            gate_dec = gate_stale = None
            if self.gating == "global":
                gate_dec, gate_stale = self._gate_decide(worker)
            max_stale, any_applied, any_credit = 0, False, False
            total_wait = 0.0
            for j, st in enumerate(shards):
                stale, applied, credit, waited = self._push_shard(
                    st, worker, payloads[j], packed, gate_dec, gate_stale)
                max_stale = max(max_stale, stale)
                any_applied = any_applied or applied
                any_credit = any_credit or credit
                total_wait += waited
            if gate_dec is not None:
                total_wait += self._gate_wait(worker, gate_dec)
                max_stale = gate_stale
            with self._metrics_lock:
                self.metrics.record_push(worker, max_stale,
                                         applied=any_applied,
                                         credit=any_credit, time=now)
                if total_wait > 0:
                    self.metrics.record_wait(worker, total_wait)
                clock = self.metrics.pushes.get(worker, -1)
            if TRACE.enabled:
                TRACE.span("push", t_push, worker=worker, clock=clock,
                           args={"staleness": max_stale,
                                 "applied": any_applied,
                                 "credit": any_credit})
        finally:
            with self._reshard_cond:
                self._inflight[cur] -= 1
                self._reshard_cond.notify_all()

    def _translate_stale(self, payloads: Sequence[Any], epoch: int,
                         cur: int) -> List[jax.Array]:
        """Re-slice per-shard gradient regions packed under a retired
        plan into the current plan's regions, chaining the retained
        migration maps epoch by epoch."""
        bufs = [np.asarray(b) for b in payloads]
        e = epoch
        while e < cur:
            mig = self._migrations.get(e)
            if mig is None:
                raise ValueError(
                    f"reshard epoch {epoch} predates the retained "
                    "migration maps; re-pull to resync")
            bufs = mig.migrate_grads(bufs)
            e += 1
        WIRE.reshard_translated += 1
        return [jnp.asarray(b) for b in bufs]

    def _push_shard(self, st: _ShardState, worker: int, payload: Any,
                    packed: bool = False,
                    gate_dec: Optional[Decision] = None,
                    gate_stale: Optional[int] = None):
        j = st.index
        with st.cond:
            now = self._clock() - self._t0
            rec = st.tracker.record_push(worker, now)
            if gate_dec is None:
                dec = st.policy.on_push(st.tracker, worker, now)
                apply_staleness = rec.staleness
            else:
                # Global gating: apply iff the gate said so, with the
                # gate's staleness (what the monolithic optimizer saw);
                # release decision belongs to the gate, not the shard.
                dec = Decision(apply_update=gate_dec.apply_update,
                               release_now=True,
                               credit_used=gate_dec.credit_used)
                apply_staleness = gate_stale
            if dec.apply_update:
                t_apply = TRACE.now() if TRACE.enabled else 0.0
                if st.retired:
                    # Mid-migration: the shard's packed state has been
                    # copied out.  Park the region; the reshard replays
                    # it through the migration map onto the NEW shards
                    # — applied exactly once, never lost.
                    self._park(st, payload, packed, apply_staleness)
                elif self.coalesce > 1:
                    self._apply_coalesced(st, payload, packed,
                                          apply_staleness)
                elif packed:
                    st.apply_packed(payload, apply_staleness)
                else:
                    st.apply(payload, apply_staleness)
                if TRACE.enabled:
                    TRACE.span("apply", t_apply, worker=worker, shard=j,
                               clock=rec.iteration)
            st.metrics.record_push(worker, rec.staleness,
                                   applied=dec.apply_update,
                                   credit=dec.credit_used, time=now)
            st.cond.notify_all()
            waited = 0.0
            if not dec.release_now:
                t_wait = TRACE.now() if TRACE.enabled else 0.0
                arrival = self._clock()
                # ``st.abandoned``: a live reshard swapped this shard
                # out — peers now push to the NEW shards, so this
                # barrier can never fill; release (the new trackers
                # were equalized, so gating stays consistent there).
                while (not self.stopped and not st.abandoned
                       and not st.policy.may_release(st.tracker, worker)):
                    st.cond.wait(timeout=0.5)
                waited = self._clock() - arrival
                rec.waited = waited
                st.metrics.record_wait(worker, waited)
                if TRACE.enabled:
                    TRACE.span("gate_wait", t_wait, worker=worker, shard=j,
                               clock=rec.iteration)
            return rec.staleness, dec.apply_update, dec.credit_used, waited

    def _make_window(self, st: _ShardState) -> CoalesceWindow:
        """One ``CoalesceWindow`` per shard (the shard's lock domain):
        ``install`` commits buffers + version together so a reader
        snapshotting (buffer, version) under ``st.cond`` never sees one
        without the other (the pull_packed cache is keyed by the
        vector)."""
        def get_pm():
            return st._packed_p, st._packed_m

        def install(p, m, n: int) -> None:
            st._packed_p, st._packed_m = p, m
            st._pieces = None
            st.version += n

        return CoalesceWindow(self, st.cond, st.optimizer, st.tracker,
                              get_pm, install)

    def _apply_coalesced(self, st: _ShardState, payload: Any,
                         packed: bool, staleness: int) -> None:
        """Route one contribution through the shard's coalescing window
        (``CoalesceWindow`` in ``repro.ps.server`` — the full flusher /
        linger / lock-release protocol lives there).  Called under
        ``st.cond``."""
        opt = st.optimizer
        scale = (1.0 / (1.0 + staleness)
                 if opt.staleness_damping else 1.0)
        if not packed:
            if not payload:              # empty shard: bookkeeping only
                st.version += 1
                return
            payload = st.plan.pack_shard_pieces(payload, st.index)
        if payload.shape[0] == 0:        # empty shard region
            st.version += 1
            return
        st.window.submit(payload, scale)

    # -- live reshard ----------------------------------------------------------
    def _park(self, st: _ShardState, payload: Any, packed: bool,
              staleness: int) -> None:
        """Called under ``st.cond`` on a retired shard: hold the packed
        gradient region for replay onto the new shards.  The retired
        shard's version does NOT move (its buffer does not change), so
        delta pulls stay truthful during the migration window."""
        if not packed:
            if not payload:
                return
            payload = st.plan.pack_shard_pieces(payload, st.index)
        if payload.shape[0] == 0:
            return
        st.parked.append((payload, int(staleness)))
        WIRE.reshard_parked += 1

    def reshard(self, n_shards: int, *, split_oversized: Optional[bool] = None,
                _mid_hook: Optional[Callable[[int], None]] = None) -> bool:
        """Live-migrate the packed store to a new shard count S'.

        Training continues throughout: each old shard is paused only for
        the copy-out under its own lock (traced as ``reshard_shard``),
        pushes racing the migration park-and-replay (see ``_park``), and
        everything else — pulls, serving, gating on not-yet-retired
        shards — proceeds.  The full protocol is documented in
        ``repro.ft.reshard``.

        Returns True if a migration ran; a same-plan call is a no-op.
        ``_mid_hook`` (tests/chaos only) fires after each shard's
        copy-out — ``FaultPlan.kill_mid_reshard`` SIGKILLs the server
        process there to exercise reshard x failover.
        """
        if self.apply_mode != "fused":
            raise ValueError("live reshard requires apply_mode='fused' "
                             "(the packed store is what migrates)")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        from repro.ft.reshard import (build_migration, equalized_counts,
                                      spread_versions)
        from repro.ft.snapshot import (capture_policy_state,
                                       restore_policy_state)
        with self._reshard_lock:
            old_plan, old_shards, old_epoch = self._plan_state()
            if n_shards == len(old_shards):
                return False
            t0 = TRACE.now() if TRACE.enabled else 0.0
            new_plan = old_plan.rebuild(
                n_shards,
                split_oversized=(self._split_oversized
                                 if split_oversized is None
                                 else split_oversized))
            mig = build_migration(old_plan, new_plan)
            # Phase 1 — retire + copy, one shard at a time.  Marking the
            # shard retired FIRST means nothing new enters its coalesce
            # window while we drain it; the copy itself is a reference
            # grab (jax arrays are immutable).  The lock hold is the
            # shard's entire migration pause.
            copied_p, copied_m, copied_v = [], [], []
            counts_per, credits_per = [], []
            for st in old_shards:
                t_s = TRACE.now() if TRACE.enabled else 0.0
                with st.cond:
                    st.retired = True
                    while (st.window is not None and not self.stopped
                           and (st.window.applying or st.window.pending)):
                        st.cond.wait(timeout=0.1)
                    copied_p.append(st._packed_p)
                    copied_m.append(st._packed_m)
                    copied_v.append(st.version)
                    counts_per.append(dict(st.tracker.counts))
                    credits_per.append(dict(st.tracker.credits))
                if TRACE.enabled:
                    TRACE.span("reshard_shard", t_s, shard=st.index)
                if _mid_hook is not None:
                    _mid_hook(st.index)
            # Phase 2 — fold params + momentum through the migration map
            # (contiguous copies in both layouts; bitwise) with no locks
            # held.  Versions redistribute sum-preserving; tracker counts
            # and credits equalize to the per-worker minimum across old
            # shards (the failover clamp rule) so the new barriers are
            # mutually consistent.
            new_p = mig.migrate(copied_p)
            new_m = mig.migrate(copied_m)
            new_versions = spread_versions(sum(copied_v), n_shards)
            eq_counts = equalized_counts(counts_per)
            eq_credits = equalized_counts(credits_per)
            pol_state = capture_policy_state(old_shards[0].policy)
            workers = sorted(eq_counts)
            new_states: List[_ShardState] = []
            for k in range(n_shards):
                policy = self._policy_factory()
                restore_policy_state(policy, pol_state)
                st = _ShardState.from_packed(
                    k, new_plan, jnp.asarray(new_p[k]),
                    jnp.asarray(new_m[k]), new_versions[k], policy,
                    self._optimizer_factory(), workers)
                st.tracker.counts.update(eq_counts)
                st.tracker.credits.update(eq_credits)
                st.window = self._make_window(st)
                new_states.append(st)
            # Phase 3 — atomic swap + epoch bump.  The old plan and the
            # map are retained so stale-epoch pushes still translate.
            with self._reshard_cond:
                self.plan = new_plan
                self.shards = new_states
                self.n_shards = n_shards
                self._retired_plans[old_epoch] = old_plan
                self._migrations[old_epoch] = mig
                self.reshard_epoch = old_epoch + 1
                self._reshard_cond.notify_all()
            with self._snap_lock:
                self._snap_key = self._snap_wire = None
            # Error-feedback state is layout-shaped; reset it (the next
            # compressed push starts a fresh feedback loop).
            self._err.clear()
            self._wire_err.clear()
            # Phase 4 — release barrier waiters stranded on old shards:
            # their peers push to the new shards now, so those barriers
            # can never fill.
            for st in old_shards:
                with st.cond:
                    st.abandoned = True
                    st.cond.notify_all()
            # Phase 5 — once no push registered under the old epoch is
            # still in flight (none can append to a parked list any
            # more), replay every parked region onto the new shards.
            with self._reshard_cond:
                while (self._inflight.get(old_epoch, 0) > 0
                       and not self.stopped):
                    self._reshard_cond.wait(timeout=0.5)
                self._inflight.pop(old_epoch, None)
            replayed = 0
            for j, st in enumerate(old_shards):
                with st.cond:
                    parked, st.parked = st.parked, []
                for region, staleness in parked:
                    self._replay_region(mig, j, region, staleness)
                    replayed += 1
            WIRE.reshard_replayed += replayed
            if TRACE.enabled:
                TRACE.span("reshard", t0,
                           args={"from": len(old_shards), "to": n_shards,
                                 "epoch": old_epoch + 1,
                                 "replayed": replayed})
            return True

    def _replay_region(self, mig, old_shard: int, region,
                       staleness: int) -> None:
        """Apply one parked old-plan gradient region to the new shards.

        The momentum fold runs ONLY over the moved segments: every
        other element of the destination shards already saw this push's
        decay through its own old shard (applied or replayed there), so
        a whole-region ``fused_update`` with zero-padding would decay
        those elements twice.
        """
        flat = np.asarray(region).reshape(-1)
        by_new: Dict[int, List[Any]] = {}
        for mv in mig.moves_from(old_shard):
            by_new.setdefault(mv.new_shard, []).append(mv)
        for k, mvs in by_new.items():
            st = self.shards[k]
            with st.cond:
                opt = st.optimizer
                scale = (1.0 / (1.0 + staleness)
                         if opt.staleness_damping else 1.0)
                p = np.asarray(st._packed_p).reshape(-1).copy()
                m = np.asarray(st._packed_m).reshape(-1).copy()
                lr = p.dtype.type(opt.lr)
                beta = p.dtype.type(opt.momentum)
                scale = p.dtype.type(scale)
                for mv in mvs:
                    g = flat[mv.old_off:mv.old_off + mv.size]
                    sl = slice(mv.new_off, mv.new_off + mv.size)
                    seg = m[sl] * beta + g * scale
                    m[sl] = seg
                    p[sl] = p[sl] - lr * seg
                rows = p.size // WIRE_LANES
                st._packed_p = jnp.asarray(p.reshape(rows, WIRE_LANES))
                st._packed_m = jnp.asarray(m.reshape(rows, WIRE_LANES))
                st._pieces = None
                # The buffer changed, so the version MUST move (delta
                # pulls diff on it) — one bump per replayed contribution
                # per touched shard.
                st.version += 1
                st.cond.notify_all()

    def _gate_decide(self, worker: int):
        """Global-gate bookkeeping + decision (no blocking yet)."""
        with self._gate_cond:
            now = self._clock() - self._t0
            rec = self._gate_tracker.record_push(worker, now)
            dec = self._gate_policy.on_push(self._gate_tracker, worker, now)
            self._gate_cond.notify_all()
            return dec, rec.staleness

    def _gate_wait(self, worker: int, dec: Decision) -> float:
        if dec.release_now:
            return 0.0
        t_wait = TRACE.now() if TRACE.enabled else 0.0
        with self._gate_cond:
            arrival = self._clock()
            while (not self.stopped
                   and not self._gate_policy.may_release(
                       self._gate_tracker, worker)):
                self._gate_cond.wait(timeout=0.5)
            waited = self._clock() - arrival
        if TRACE.enabled:
            TRACE.span("gate_wait", t_wait, worker=worker)
        return waited

    def _compress(self, worker: int,
                  pieces_per_shard: List[List[jax.Array]]):
        err = self._err.get(worker)
        if err is None:
            err = [self.compressor.init_error(p) for p in pieces_per_shard]
        out = []
        for j, pieces in enumerate(pieces_per_shard):
            compressed, err[j] = self.compressor.apply(pieces, err[j])
            out.append(compressed)
        self._err[worker] = err
        return out

    def _compress_packed(self, worker: int,
                         shard_bufs: List[jax.Array]) -> List[jax.Array]:
        """Fused wire compression: ONE kernel launch per non-empty shard
        (quantize + dequant + error feedback in a single VMEM pass),
        with per-(worker, shard) f32 error buffers in wire layout."""
        return [self._compress_packed_one(worker, j, buf)
                for j, buf in enumerate(shard_bufs)]

    def _compress_packed_one(self, worker: int, shard: int,
                             buf: jax.Array) -> jax.Array:
        if buf.shape[0] == 0:
            return buf
        state = self._wire_err.setdefault(worker, {})
        err = state.get(shard)
        if err is None:
            err = jnp.zeros(buf.shape, jnp.float32)
        buf, err = self.wire_compression.apply(buf, err)
        state[shard] = err
        return buf

    def record_loss(self, step: int, loss: float) -> None:
        with self._metrics_lock:
            now = self._clock() - self._t0
            self.metrics.record_loss_point(now, self.version, float(loss))

    # -- elastic membership ----------------------------------------------------
    def add_worker(self, worker: int) -> None:
        for st in self.shards:
            with st.cond:
                st.tracker.add_worker(worker)
                st.metrics.n_workers = len(st.tracker.workers)
                st.cond.notify_all()
        if self.gating == "global":
            with self._gate_cond:
                self._gate_tracker.add_worker(worker)
                self._gate_cond.notify_all()
        with self._metrics_lock:
            self.metrics.n_workers = len(self.shards[0].tracker.workers)
        self._err.pop(worker, None)
        self._wire_err.pop(worker, None)

    def remove_worker(self, worker: int) -> None:
        """Departure must not stall ANY shard's barrier: drop the worker
        from every shard tracker, waking that shard's waiters."""
        for st in self.shards:
            with st.cond:
                st.tracker.remove_worker(worker)
                st.metrics.n_workers = len(st.tracker.workers)
                st.cond.notify_all()
        if self.gating == "global":
            with self._gate_cond:
                self._gate_tracker.remove_worker(worker)
                self._gate_cond.notify_all()
        with self._metrics_lock:
            self.metrics.n_workers = len(self.shards[0].tracker.workers)
        self._err.pop(worker, None)
        self._wire_err.pop(worker, None)

    def stop(self) -> None:
        self.stopped = True
        for st in self.shards:
            with st.cond:
                st.cond.notify_all()
        if self.gating == "global":
            with self._gate_cond:
                self._gate_cond.notify_all()

    # -- inspection ------------------------------------------------------------
    # (``params``/``snapshot``/``shutdown`` come from the protocol base.)
    @property
    def version(self) -> int:
        """Total applied shard-updates.  At S=1 this equals the monolithic
        server's version (one applied update per released push)."""
        return sum(st.version for st in self.shards)

    def shard_versions(self) -> List[int]:
        return [st.version for st in self.shards]

    def staleness_profile(self) -> Dict[int, Dict[int, int]]:
        """shard -> worker -> current gap."""
        out = {}
        for st in self.shards:
            with st.cond:
                out[st.index] = st.tracker.staleness_profile()
        return out

    def shard_metrics(self) -> List[RunMetrics]:
        return [st.metrics for st in self.shards]
