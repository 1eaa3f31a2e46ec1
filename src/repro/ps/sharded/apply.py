"""The server's apply: its settings and the coalescing window.

Every shard folds a pushed gradient region into its resident packed
parameters and momentum through the fused Pallas momentum kernel
(``repro.kernels.ops.fused_update``):

    m' = momentum * m + scale * g        p' = p - lr * m'

``ServerOptimizer`` holds the three settings of that step; the paper
uses plain SGD on the server (workers send raw gradients), and
``staleness_damping`` scales a push by 1/(1+staleness)
(Omnivore-style momentum tempering, §II related work).

Kernel imports stay inside the calls so ``import repro.ps`` never pulls
in the Pallas kernel stack.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.obs.trace import TRACE
from repro.perfcount import WIRE

#: Default linger (seconds) a coalescing flusher waits for its window
#: to fill before launching a partial batch — small enough to vanish
#: next to a training step, large enough for concurrently-pushing
#: workers to land in one batch.
DEFAULT_COALESCE_WAIT_S = 0.05


@dataclasses.dataclass
class ServerOptimizer:
    """Momentum SGD settings of the fused apply, with optional
    staleness damping."""

    lr: float
    momentum: float = 0.0
    staleness_damping: bool = False

    def scale(self, staleness: int) -> float:
        """The gradient's weight in the momentum fold."""
        return 1.0 / (1.0 + staleness) if self.staleness_damping else 1.0


class CoalesceWindow:
    """Bounded micro-batching window over packed applies.

    One instance per lock domain (one shard of the server).  ``submit``
    is called UNDER ``cond`` with one contribution; the first
    contributor with no flush in flight becomes the *flusher*: it
    lingers up to ``server.coalesce_wait`` for the window to fill
    (capped at the live worker count — a window larger than the barrier
    group can ever fill would stall every round), drains up to
    ``server.coalesce`` contributions, and folds them through ONE
    ``fused_update_batched`` launch in enqueue order.  The kernel
    dispatch runs with ``cond`` RELEASED so concurrent pushes can queue
    into the next window (that concurrency IS the batching); ``install``
    re-installs the buffers and the version bump together under the
    lock, so readers never observe a version that does not match the
    resident buffer.  Later contributors wait until the flusher has
    applied their sequence number.

    ``server`` supplies the live knobs (``coalesce``,
    ``coalesce_wait``, ``stopped``, ``_clock``); ``get_pm`` returns the
    resident (params, momentum) wire buffers; ``install(p, m, n)``
    commits them plus an ``n``-contribution version bump (called under
    ``cond``).
    """

    def __init__(self, server, cond, optimizer, tracker, get_pm,
                 install):
        self.server = server
        self.cond = cond
        self.optimizer = optimizer
        self.tracker = tracker
        self.get_pm = get_pm
        self.install = install
        self.pending: list = []      # (wire_g, scale) tuples
        self.applying = False        # a flusher owns the window
        self.enq_seq = 0             # contributions ever queued
        self.applied_seq = 0         # contributions ever applied

    def submit(self, wire_g, scale: float) -> None:
        """Queue one contribution (called under ``cond``) and return
        once it has been applied."""
        srv = self.server
        self.pending.append((wire_g, scale))
        self.enq_seq += 1
        my_seq = self.enq_seq
        self.cond.notify_all()       # wake a lingering flusher
        if self.applying:
            while self.applied_seq < my_seq and not srv.stopped:
                self.cond.wait(timeout=0.5)
            return
        self.applying = True
        try:
            # The fill target is capped at the LIVE worker count and
            # recomputed on every wake-up: a worker removed while the
            # flusher lingers (its seat freed by ``remove_worker``,
            # which notifies this cond) shrinks the target immediately
            # instead of stalling the flush for the full linger on a
            # window that can no longer fill.
            def window() -> int:
                return max(1, min(srv.coalesce, len(self.tracker.workers)))

            while self.pending and not srv.stopped:
                if srv.coalesce_wait > 0.0 and len(self.pending) < window():
                    deadline = srv._clock() + srv.coalesce_wait
                    while (len(self.pending) < window()
                           and not srv.stopped):
                        remaining = deadline - srv._clock()
                        if remaining <= 0:
                            break
                        self.cond.wait(timeout=remaining)
                batch = self.pending[:srv.coalesce]
                del self.pending[:len(batch)]
                self._flush(batch)
        finally:
            self.applying = False
            self.cond.notify_all()

    def _flush(self, batch: list) -> None:
        """One batched launch over ``batch`` (called under ``cond``;
        drops the lock for the kernel dispatch)."""
        from repro.kernels import ops as kops
        t0 = TRACE.now() if TRACE.enabled else 0.0
        opt = self.optimizer
        bufs = [b for b, _ in batch]
        scales = [s for _, s in batch]
        p, m = self.get_pm()
        self.cond.release()
        try:
            gs = bufs[0][None] if len(bufs) == 1 else jnp.stack(bufs)
            new_p, new_m = kops.fused_update_batched(
                p, m, gs, lr=opt.lr, beta=opt.momentum, scales=scales)
        finally:
            self.cond.acquire()
        self.install(new_p, new_m, len(batch))
        self.applied_seq += len(batch)
        if len(batch) > 1:
            WIRE.apply_launches_saved += len(batch) - 1
        if TRACE.enabled:
            TRACE.span("coalesce_flush", t0, args={"n": len(batch)})
        self.cond.notify_all()
