"""Worker threads for the parameter-server layer.

Each worker runs the paper's loop (Alg. 1 worker block):

    pull w_s  →  replace local weights  →  compute grads on a mini-batch
    →  push grads  →  (blocked until the server sends OK)

``step_fn`` is any jitted ``(params, batch) -> (grads, aux)`` function;
batches come from a per-worker data shard (data parallelism, §I).  A
``speed_factor > 1`` makes the worker proportionally slower by sleeping
``(speed_factor − 1) × measured_compute`` per iteration — this emulates
the paper's heterogeneous cluster (GTX1060 vs GTX1080Ti) on one machine
without depending on scheduler noise.

``wire_format='packed'`` switches the worker onto the zero-repack hot
path: it pulls the server's packed (rows, 512) wire buffer
(``pull_packed``), hands it to ``step_fn`` unchanged (the jitted step
unpacks, differentiates and re-packs in one fused program — see
``repro.launch.train.train_ps``), and pushes the packed gradient buffer
back (``push_packed``).  The pytree<->wire boundary is crossed exactly
once per direction, inside the worker's jit.

``delta_pull=True`` (packed only) replaces the full-snapshot pull with
``server.pull_delta``: the worker keeps a resident packed buffer plus
the per-shard version vector from its last pull and patches in only
the shard regions that advanced — pull bytes proportional to change.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Iterator, Optional

from repro._compat import warn_legacy
from repro.api.protocol import ParameterServerProtocol
from repro.obs.trace import TRACE
from repro.perfcount import WIRE, add_counts

StepFn = Callable[[Any, Any], Any]  # (params, batch) -> (grads, aux)


class PSWorker(threading.Thread):
    def __init__(self, worker_id: int, server: ParameterServerProtocol,
                 step_fn: StepFn, batches: Iterator[Any], n_iterations: int,
                 *, speed_factor: float = 1.0,
                 loss_from_aux: Optional[Callable[[Any], float]] = None,
                 wire_format: str = "tree",
                 delta_pull: bool = False,
                 reconnect: Optional[Callable[[], Any]] = None,
                 name: Optional[str] = None):
        super().__init__(name=name or f"ps-worker-{worker_id}", daemon=True)
        warn_legacy("PSWorker",
                    "repro.api.build_session (sessions construct and "
                    "join their own workers)")
        if wire_format not in ("tree", "packed"):
            raise ValueError(f"unknown wire format {wire_format!r}")
        if delta_pull and wire_format != "packed":
            raise ValueError("delta_pull tracks per-shard versions of "
                             "the packed snapshot; it requires "
                             "wire_format='packed'")
        self.worker_id = worker_id
        self.server = server
        self.step_fn = step_fn
        self.batches = batches
        self.n_iterations = n_iterations
        self.speed_factor = speed_factor
        self.loss_from_aux = loss_from_aux
        self.wire_format = wire_format
        self.delta_pull = delta_pull
        #: Failover hook (``repro.ft``): a zero-arg callable returning a
        #: fresh server handle after the current one dies with a
        #: ``ConnectionError`` — the worker rebinds pull/push against it
        #: and retries the interrupted iteration.  ``None`` = die.
        self.reconnect = reconnect
        self.reconnects = 0
        self.iterations_done = 0
        #: JAX platform the step's output landed on (set by the first step)
        self.platform = ""
        self.failure: Optional[BaseException] = None
        self._abort = threading.Event()

    def abort(self) -> None:
        """Simulate a node failure: the worker exits before its next pull."""
        self._abort.set()

    def _delta_puller(self):
        """Version-delta pulls: keep a resident HOST buffer and patch
        only the shard regions whose version advanced since the last
        pull, in place (the bootstrap vector of -1s makes the first
        delta carry every shard).  An empty delta returns the previous
        device buffer untouched — zero copies; a non-empty one costs
        one device upload of the patched buffer (per-region ``.at[]``
        scatters would copy the whole buffer once per region).  Returns
        a drop-in replacement for ``server.pull_packed``."""
        import jax.numpy as jnp
        import numpy as np

        from repro.wireformat import WIRE_LANES
        layout = self.server.plan.wire_layout()
        state = {
            "layout": layout,
            "host": np.zeros((layout.total_rows, WIRE_LANES),
                             layout.dtype),
            "wire": None,
            "versions": (-1,) * getattr(self.server, "n_shards", 1),
        }

        def pull(worker_id: int):
            d = self.server.pull_delta(worker_id, state["versions"])
            while len(d.versions) != len(state["versions"]):
                # Live reshard: the server's arity moved under us.
                # Rebuild the resident buffer against the server's
                # CURRENT plan and re-bootstrap; if the plan moves yet
                # again between reply and rebuild, the loop resyncs
                # once more.  (In-heap workers share the plan object
                # graph with the server, so ``server.plan`` IS the new
                # plan.)
                lay = self.server.plan.wire_layout()
                state["layout"] = lay
                state["host"] = np.zeros((lay.total_rows, WIRE_LANES),
                                         lay.dtype)
                state["wire"] = None
                state["versions"] = (-1,) * len(lay.shard_row_start)
                d = self.server.pull_delta(worker_id, state["versions"])
            state["versions"] = d.versions
            if state["wire"] is not None and d.empty:
                return state["wire"]
            for j, region in zip(d.shards, d.regions):
                start = state["layout"].shard_row_start[j]
                state["host"][start:start + region.shape[0]] = \
                    np.asarray(region)
            # jnp.array COPIES (asarray may alias on CPU, and the host
            # buffer mutates in place on the next pull)
            state["wire"] = jnp.array(state["host"])
            WIRE.h2d_bytes += state["host"].nbytes
            return state["wire"]

        return pull

    def _bind(self):
        """(pull, push) against the CURRENT ``self.server`` — re-run
        after a reconnect swaps the handle."""
        packed = self.wire_format == "packed"
        pull = (self._delta_puller() if packed and self.delta_pull
                else self.server.pull_packed if packed
                else self.server.pull)
        push = self.server.push_packed if packed else self.server.push
        return pull, push

    def run(self) -> None:
        pull, push = self._bind()
        try:
            it = 0
            while it < self.n_iterations:
                if self._abort.is_set() or self.server.stopped:
                    break
                try:
                    params = pull(self.worker_id)
                    WIRE.pulls += 1
                    t_tr = TRACE.now() if TRACE.enabled else 0.0
                    t0 = time.monotonic()
                    batch = next(self.batches)
                    if TRACE.enabled:
                        TRACE.span("batch", t_tr, worker=self.worker_id,
                                   clock=it)
                    t_call = TRACE.now() if TRACE.enabled else 0.0
                    grads, aux = self.step_fn(params, batch)
                    if TRACE.enabled:
                        TRACE.span("dispatch", t_call,
                                   worker=self.worker_id, clock=it)
                    t_wait = TRACE.now() if TRACE.enabled else 0.0
                    grads = _block(grads)
                    compute = time.monotonic() - t0
                    if TRACE.enabled:
                        TRACE.span("device_wait", t_wait,
                                   worker=self.worker_id, clock=it)
                        TRACE.span("compute_step", t_tr,
                                   worker=self.worker_id, clock=it)
                    if not self.platform:
                        self.platform = _platform(grads)
                    if self.speed_factor > 1.0:
                        # The sleep IS the emulated (slower-device)
                        # compute: its own span, which summarize counts
                        # as compute.
                        t_sleep = TRACE.now() if TRACE.enabled else 0.0
                        time.sleep(compute * (self.speed_factor - 1.0))
                        if TRACE.enabled:
                            TRACE.span("straggler_sleep", t_sleep,
                                       worker=self.worker_id, clock=it)
                    if isinstance(aux, dict) and "counts" in aux:
                        # one transfer brings the loss and the counts
                        aux = _host(aux)
                        add_counts(aux["counts"])
                    if self.loss_from_aux is not None:
                        self.server.record_loss(it,
                                                self.loss_from_aux(aux))
                    push(self.worker_id, grads)
                except ConnectionError:
                    # The server handle died mid-iteration.  With a
                    # failover hook: swap in a fresh handle, rebind, and
                    # retry the SAME iteration (its push may double —
                    # ordinary async-SGD noise, never lost progress).
                    if self.reconnect is None:
                        raise
                    self.server = self.reconnect()
                    pull, push = self._bind()
                    self.reconnects += 1
                    continue
                self.iterations_done += 1
                it += 1
        except BaseException as e:  # surfaced by join_all
            self.failure = e
        finally:
            # Leave the barrier group on ANY exit — completion, abort or
            # crash.  A departed worker must not gate survivors (fault
            # tolerance) nor stall late joiners (elasticity).
            self.server.remove_worker(self.worker_id)


def _block(tree: Any) -> Any:
    import jax
    return jax.block_until_ready(tree)


def _host(tree: Any) -> Any:
    """The step's small outputs on the host, in one transfer."""
    import jax
    return jax.device_get(tree)


def _platform(tree: Any) -> str:
    import jax
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return next(iter(leaf.devices())).platform


def run_cluster(server: ParameterServerProtocol, workers: list[PSWorker],
                timeout: float = 600.0) -> None:
    """Start all workers, join them, re-raise the first worker failure."""
    for w in workers:
        w.start()
    deadline = time.monotonic() + timeout
    for w in workers:
        w.join(timeout=max(0.0, deadline - time.monotonic()))
    server.stop()
    for w in workers:
        w.join(timeout=5.0)
        if w.failure is not None:
            raise w.failure
    alive = [w.name for w in workers if w.is_alive()]
    if alive:
        raise TimeoutError(f"workers did not finish: {alive}")
