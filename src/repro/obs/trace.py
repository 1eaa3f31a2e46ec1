"""Process-local bounded ring-buffer trace recorder.

One module-global recorder (``TRACE``) per process; every hook in the
hot path is written as

    t0 = TRACE.now() if TRACE.enabled else 0.0
    ... the traced region ...
    if TRACE.enabled:
        TRACE.span("push", t0, worker=w, clock=it, args={...})

so a build that never enables tracing pays exactly one attribute read
per hook site, and a *call* on the disabled recorder is a single
early-return (``benchmarks/obs_overhead.py`` measures both and
``perf_gate.py`` gates the trajectory).

Design constraints, in order:

  * **Bounded.**  Events land in a ``collections.deque(maxlen=...)`` —
    a run that out-produces its drain cadence silently drops its
    *oldest* events instead of growing without bound.
  * **Cheap.**  The enabled fast path is one ``perf_counter`` read, one
    counter bump and one tuple append (all GIL-atomic enough for the
    server's many pushing threads; the per-recorder ``seq`` comes from
    ``itertools.count``, whose ``__next__`` is atomic in CPython).
  * **Mergeable.**  Timestamps are monotonic (``time.perf_counter``)
    while recording and converted to *wall-clock* seconds on ``drain``
    using the wall/mono anchor captured at ``enable`` — so ring
    buffers drained from different processes land on one comparable
    time axis, and ordering within a process never goes backwards.

Event record (the dict ``drain`` emits; also the JSONL line format):

    {"seq": int,          # per-recorder monotone id (dedup key)
     "ts": float,         # wall-clock seconds (start of the span)
     "dur": float,        # seconds; 0.0 for instant events
     "name": str,         # see EVENT_NAMES
     "worker": int,       # -1 when not worker-scoped
     "shard": int,        # -1 when not shard-scoped
     "clock": int,        # worker iteration / push count; -1 unknown
     "src": str,          # recorder source ("server", "w0", ...)
     "args": dict}        # optional event payload

Stdlib-only on purpose: spawned workers and CLI tooling import this
without jax anywhere near the path.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro import perfcount

#: Ring capacity when ``enable`` is not given one.  At ~100 bytes per
#: event tuple this bounds a recorder around a few MB.
DEFAULT_CAPACITY = 65536

#: The typed event vocabulary (exporters and ``summarize`` key on it).
EVENT_NAMES = (
    "push",              # span: one gated push, server side
    "gate_wait",         # span: time blocked in the Algorithm-1 gate
    "apply",             # span: one optimizer apply (tree or fused);
                         #       times the host enqueue, not the device
    "coalesce_flush",    # span: one batched fused_update_batched launch
    "pull",              # span: full-snapshot pull (host enqueue)
    "pull_delta",        # span: version-delta pull
    "batch",             # span: the next batch made and put on the device
    "compute_step",      # span: one worker forward/backward iteration,
                         #       from its batch to the gradient on hand
    "dispatch",          # span: the jitted step call until it returns
                         #       (inside compute_step)
    "device_wait",       # span: blocked on the step's result
                         #       (inside compute_step)
    "straggler_sleep",   # span: a slowed worker's emulated extra compute
    "dssp_decision",     # instant: Algorithm-1/2 gate decision (DSSP)
    "frame_tx",          # instant: one encoded transport frame
    "frame_rx",          # instant: one decoded transport frame
    "metrics_snapshot",  # instant: periodic MetricsSampler sample
    "snapshot",          # span: one whole server checkpoint (repro.ft)
    "snapshot_shard",    # span: one shard's state grab UNDER its lock —
                         #       the only pause a snapshot imposes
    "reshard",           # span: one live reshard, swap to replay
    "reshard_shard",     # span: one old shard retired under its lock
    "reconnect",         # span: a client's backoff reconnect loop
    "failover",          # span: server restart-and-resume from a snapshot
    "fault",             # instant: one injected FaultPlan event
    "replica_refresh",   # span: a serving replica's delta-pull refresh
    "decode_batch",      # span: one continuously-batched decode call
    "staleness_block",   # span: admission blocked on the serve-side
                         #       SSP gate until a fresh refresh landed
)

#: Device phases: the ``jax.named_scope`` names the jitted code puts on
#: its ops (``repro.obs.device.phase``), so a device trace can say which
#: part of the program each device-second belongs to.
PHASES = (
    "wire",       # packed-wire glue: unpack/pack in the step, pull concat
    "attn_fwd",   # the attention kernel, forward and remat recompute
    "attn_bwd",   # the attention backward
    "optimizer",  # the SPMD step's optimizer update and delay ring
    "apply",      # the server's fused momentum apply
    "moe_route",    # the expert router: scores, top-k, sort, row gathers
    "moe_experts",  # the held experts' grouped matmuls, fwd and bwd
)

#: Jitted programs that are one phase as a whole, by function name: an
#: op of theirs belongs to the phase without a scope inside (a scope
#: would rename the apply kernel's custom call).
PHASE_PROGRAMS = {
    "_fused_update": "apply",
    "_fused_update_batched": "apply",
    "apply_region": "apply",
    "wire_concat": "wire",
}

#: What a fold of device ops by phase reports besides ``PHASES``: the
#: rest of a differentiated step's forward and backward (told apart by
#: the ``transpose(`` JAX puts on the backward's op path), and ops under
#: no phase at all.
FOLD_PHASES = PHASES + ("fwd", "bwd", "unscoped")


class TraceRecorder:
    """Bounded, process-local, thread-tolerant event ring."""

    __slots__ = ("enabled", "source", "capacity", "_events", "_seq",
                 "_wall0", "_mono0", "_lock", "_hooks", "_counters")

    def __init__(self) -> None:
        self.enabled = False
        self.source = ""
        self.capacity = DEFAULT_CAPACITY
        self._events: collections.deque = collections.deque(
            maxlen=DEFAULT_CAPACITY)
        self._seq = itertools.count()
        self._wall0 = 0.0
        self._mono0 = 0.0
        self._lock = threading.Lock()
        self._hooks: List[Callable[[str], None]] = []
        self._counters: List[Optional[Dict]] = [None, None]

    # -- lifecycle -------------------------------------------------------
    def enable(self, source: str = "server",
               capacity: int = DEFAULT_CAPACITY) -> None:
        """Arm the recorder: fresh ring, fresh seq, wall/mono anchor."""
        with self._lock:
            self.source = source
            self.capacity = int(capacity)
            self._events = collections.deque(maxlen=self.capacity)
            self._seq = itertools.count()
            self._wall0 = time.time()
            self._mono0 = time.perf_counter()
            self._counters = [perfcount.snapshot_all(), None]
            self.enabled = True
        for hook in list(self._hooks):
            hook("enable")

    def disable(self) -> None:
        """Stop recording and drop anything not yet drained."""
        with self._lock:
            self.enabled = False
            self._events.clear()
            if self._counters[0] is not None and self._counters[1] is None:
                self._counters[1] = perfcount.snapshot_all()
        for hook in list(self._hooks):
            hook("disable")

    def add_hook(self, hook: Callable[[str], None]) -> None:
        """Call ``hook("enable")`` / ``hook("disable")`` each time the
        recorder is armed or stopped (once per distinct hook)."""
        if hook not in self._hooks:
            self._hooks.append(hook)

    def window_counters(self) -> Dict[str, Dict[str, int]]:
        """The ``repro.perfcount`` counters' deltas over the last armed
        interval: ``enable`` to ``disable``, or to now while armed;
        ``{}`` if the recorder was never armed."""
        before, after = self._counters
        if before is None:
            return {}
        after = after if after is not None else perfcount.snapshot_all()
        return {group: {k: v - before[group].get(k, 0)
                        for k, v in now.items()}
                for group, now in after.items()}

    # -- recording (the hot path) ----------------------------------------
    def now(self) -> float:
        """Span start timestamp (monotonic; pair with ``span``)."""
        return time.perf_counter()

    def instant(self, name: str, *, worker: int = -1, shard: int = -1,
                clock: int = -1,
                args: Optional[Dict[str, Any]] = None) -> None:
        """Record a zero-duration event; no-op while disabled."""
        if not self.enabled:
            return
        self._events.append((next(self._seq), time.perf_counter(), 0.0,
                             name, worker, shard, clock, args))

    def span(self, name: str, t0: float, *, worker: int = -1,
             shard: int = -1, clock: int = -1,
             args: Optional[Dict[str, Any]] = None) -> None:
        """Record a span started at ``t0`` (= an earlier ``now()``)
        ending now; no-op while disabled."""
        if not self.enabled:
            return
        dur = time.perf_counter() - t0
        self._events.append((next(self._seq), t0, dur, name, worker,
                             shard, clock, args))

    # -- draining --------------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def drain(self) -> List[Dict[str, Any]]:
        """Swap the ring out and return its events as wall-clock dicts.

        Safe to call while recording continues: the swap happens under
        the lock; an append racing the swap lands in whichever ring it
        grabbed first (at most a handful of events slide to the next
        drain — never lost, never duplicated).
        """
        with self._lock:
            if not self._events:
                return []
            batch = self._events
            self._events = collections.deque(maxlen=self.capacity)
            wall0, mono0, src = self._wall0, self._mono0, self.source
        out = []
        for seq, t0, dur, name, worker, shard, clock, args in batch:
            e: Dict[str, Any] = {
                "seq": seq,
                "ts": wall0 + (t0 - mono0),
                "dur": dur,
                "name": name,
                "worker": worker,
                "shard": shard,
                "clock": clock,
                "src": src,
            }
            if args:
                e["args"] = args
            out.append(e)
        return out


#: The process-global recorder every instrumented site writes through.
TRACE = TraceRecorder()
