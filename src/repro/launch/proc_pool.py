"""Process worker pool: real OS processes driving the PS over a transport.

The threaded ``PSWorker`` shares a Python heap with the server, so the
packed wire buffer never actually crosses a process boundary and
stragglers are simulated with sleeps against GIL-released compute.
``ProcessWorkerPool`` spawns N *processes* instead: each one rebuilds
the model deterministically from its ``WorkerTask`` spec (same
``PRNGKey(0)`` init and ``ShardPlan`` as the parent — the plan is pure
metadata, so both sides derive identical wire layouts), connects to the
server's transport address, and runs the paper's worker loop

    pull packed params -> jitted step (unpack, grad, re-pack) ->
    push packed grads -> blocked until the sync policy releases it

entirely in frame bytes.  A per-worker ``slowdown`` factor sleeps
``(slowdown - 1) x measured_compute`` per iteration, which now creates
*genuine* heterogeneous stragglers — separate interpreters, separate
GILs, real wire in between — the regime DSSP's dynamic threshold is
designed for.

Workers are spawned (never forked): forking a process with a live JAX
runtime is undefined behavior, and spawn also matches how a multi-host
deployment would launch ranks.
"""

from __future__ import annotations

import dataclasses
import multiprocessing
import os
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence

from repro.launch.runtime import pin_child_platform


@dataclasses.dataclass(frozen=True)
class WorkerTask:
    """Everything a spawned worker needs to rebuild its half of the run.

    Must stay picklable and small — it crosses the spawn boundary, the
    weights do not (the worker pulls them over the transport).
    """

    arch: str                 # repro.configs key, e.g. "xlstm-125m"
    n_shards: int             # parent's ShardPlan arity (layout must match)
    n_iterations: int
    smoke: bool = True
    kernels: str = "auto"     # model.kernels dispatch string (registry)
    seq_len: int = 64
    global_batch: int = 8
    data_seed: int = 0        # worker w streams shard seed data_seed+1+w
    compress: str = "none"    # frame-level wire compression (int8)
    delta_pull: bool = False  # version-delta pulls over PULL_DELTA frames
    trace: bool = False       # arm the worker's repro.obs ring buffer
    trace_spill: str = ""     # dir for the per-worker JSONL spill file
    trace_flush_every: int = 32  # iterations between TRACE-frame flushes
    # -- fault tolerance (repro.ft) ---------------------------------
    reconnect_tries: int = 0  # per-outage reconnect budget (0 = die)
    reconnect_base_s: float = 0.1
    reconnect_max_s: float = 2.0
    fault_plan: Optional[Dict[str, Any]] = None  # FaultPlan.to_dict()

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_spec(cls, spec, n_iterations: int, *, trace_spill: str = "",
                  trace_flush_every: int = 32) -> "WorkerTask":
        """Derive the spawn payload from a ``repro.api.RunSpec``.

        Only the int8 compression rides the frames (bytes shrink on the
        OS wire; the codec dequantizes on receipt) — topk has no
        frame-level encoding and stays a server-side pass.

        ``n_shards`` is clamped to >= 1: a monolithic spec may carry
        ``ps.shards=0`` (the ServerSpec default), but the worker-side
        ``build_shard_plan`` — and the mono server's own packed plan —
        are single-shard.
        """
        return cls(arch=spec.model.arch,
                   n_shards=max(1, spec.ps.shards),
                   n_iterations=n_iterations,
                   smoke=spec.model.smoke,
                   kernels=spec.model.kernels,
                   seq_len=spec.data.seq_len,
                   global_batch=spec.data.global_batch,
                   data_seed=spec.data.seed,
                   compress=("int8" if spec.wire.compression == "int8"
                             else "none"),
                   delta_pull=spec.wire.delta_pull,
                   trace=bool(getattr(spec, "obs", None)
                              and spec.obs.trace),
                   trace_spill=trace_spill,
                   trace_flush_every=trace_flush_every,
                   reconnect_tries=spec.ft.reconnect_tries,
                   reconnect_base_s=spec.ft.reconnect_base_s,
                   reconnect_max_s=spec.ft.reconnect_max_s,
                   fault_plan=(spec.ft.fault_plan().to_dict()
                               if spec.ft.faults else None))


@dataclasses.dataclass
class WorkerResult:
    worker_id: int
    iterations_done: int
    error: Optional[str] = None      # traceback text for failed workers
    exitcode: Optional[int] = None
    platform: str = ""               # the JAX backend the worker ran on


def _worker_main(task: Dict[str, Any], address, worker_id: int,
                 slowdown: float, queue) -> None:
    """Entry point of one spawned worker process."""
    done = 0
    platform = ""
    try:
        pin_child_platform()
        import functools

        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.configs import get_config, get_smoke_config
        from repro.data.synthetic import DataConfig, batches
        from repro.models import registry
        from repro.perfcount import WIRE
        from repro.ps.sharded.plan import build_shard_plan
        from repro.transport import connect
        from repro.wireformat import WIRE_LANES, FrameError

        platform = jax.default_backend()
        cfg = (get_smoke_config(task["arch"]) if task["smoke"]
               else get_config(task["arch"]))
        if task.get("kernels", "auto") != cfg.kernels:
            cfg = dataclasses.replace(cfg, kernels=task["kernels"])
        data_cfg = DataConfig(vocab_size=cfg.vocab_size,
                              seq_len=task["seq_len"],
                              global_batch=task["global_batch"],
                              seed=task["data_seed"] + 1 + worker_id)
        loss_fn = registry.loss_fn(cfg)
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        plan = build_shard_plan(params, task["n_shards"])
        layout = plan.wire_layout()
        del params  # the live weights come over the wire

        def make_step(plan):
            @functools.partial(jax.jit, donate_argnums=(1,))
            def packed_step(wire_p, wire_g_prev, batch):
                p = plan.unpack(wire_p)
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, batch)
                return wire_g_prev.at[:].set(plan.pack(grads)), loss
            return packed_step

        packed_step = make_step(plan)

        from repro.ft.backoff import BackoffPolicy
        from repro.ft.faults import FaultPlan, kill_self, wrap_channel
        from repro.transport.base import TransportClosed

        fault_plan = FaultPlan.from_dict(task.get("fault_plan"))
        reconnect_tries = int(task.get("reconnect_tries", 0))
        reconnect_policy = (BackoffPolicy(
            base_s=task.get("reconnect_base_s", 0.1), factor=2.0,
            max_s=task.get("reconnect_max_s", 2.0),
            max_tries=reconnect_tries) if reconnect_tries > 0 else None)

        tracer = spill_fh = None
        if task.get("trace"):
            from repro.obs.trace import TRACE as tracer
            tracer.enable(source=f"w{worker_id}")
            if task.get("trace_spill"):
                # Append-mode JSONL spill: every drained batch lands on
                # disk BEFORE the frame send, so a worker killed mid-run
                # leaves its events recoverable (collector dedups the
                # ones that also made it over the wire).
                os.makedirs(task["trace_spill"], exist_ok=True)
                spill_fh = open(os.path.join(task["trace_spill"],
                                             f"w{worker_id}.jsonl"),
                                "a", encoding="utf-8")

        client = connect(address, worker_id, compress=task["compress"])
        if fault_plan.wants_channel:
            # Drop/delay faults wrap the live channel AND the factory,
            # so a post-reconnect channel stays faulty too.
            client.channel = wrap_channel(client.channel, fault_plan, worker_id)
            inner_factory = client.channel_factory
            if inner_factory is not None:
                client.channel_factory = (
                    lambda: wrap_channel(inner_factory(), fault_plan, worker_id))

        def flush_trace() -> None:
            if tracer is None:
                return
            events = tracer.drain()
            if not events:
                return
            if spill_fh is not None:
                import json
                for e in events:
                    spill_fh.write(json.dumps(e, separators=(",", ":")))
                    spill_fh.write("\n")
                spill_fh.flush()
            try:
                client.send_trace(events)
            except Exception:
                pass  # server gone — the spill file still has them

        rows = client.hello()
        if rows != layout.total_rows:
            raise ValueError(
                f"server wire layout has {rows} rows, local plan derives "
                f"{layout.total_rows} — task spec out of sync with server")
        wire_g = jnp.zeros((layout.total_rows, WIRE_LANES), layout.dtype)
        stream = batches(cfg, data_cfg)
        # Version-delta pulls keep a RESIDENT host-side buffer: only the
        # shard regions whose version advanced since the last pull cross
        # the wire, and they are patched into the buffer in place.
        delta_pull = bool(task.get("delta_pull"))
        wire_host = np.zeros((layout.total_rows, WIRE_LANES),
                             layout.dtype) if delta_pull else None
        versions = (-1,) * task["n_shards"]
        row_start = layout.shard_row_start
        epoch = client.reshard_epoch

        def rebuild_layout(n_shards: int, new_epoch: int) -> None:
            """Live-reshard rebuild: the server's shard arity changed
            under us (delta reply carried a new epoch / a different
            version-vector length).  Re-derive the plan at the new
            arity — ``rebuild`` only needs leaf shapes, never weights —
            re-jit the step, and re-size every layout-shaped buffer.
            The triggering reply is a full snapshot in the NEW layout,
            so patching it into the fresh host buffer is complete."""
            nonlocal plan, layout, row_start, wire_host, wire_g
            nonlocal packed_step, epoch
            plan = plan.rebuild(n_shards)
            layout = plan.wire_layout()
            row_start = layout.shard_row_start
            if wire_host is not None:
                wire_host = np.zeros((layout.total_rows, WIRE_LANES),
                                     layout.dtype)
            wire_g = jnp.zeros((layout.total_rows, WIRE_LANES),
                               layout.dtype)
            packed_step = make_step(plan)
            epoch = new_epoch
            # Future pushes are packed against the NEW layout; stamp
            # the epoch they should be applied under.
            client.reshard_epoch = new_epoch
        try:
            it = 0
            while it < task["n_iterations"]:
                if fault_plan.worker_kill_due(worker_id, it):
                    flush_trace()
                    kill_self()  # pragma: no cover - process dies here
                try:
                    # copy=True (the default): on CPU, jnp.asarray may
                    # ALIAS host memory instead of copying, and a device
                    # buffer aliasing the shmem slot would outlive the
                    # RPC lifetime contract (and pin the mapping at
                    # close).
                    if delta_pull:
                        d = client.pull_delta(versions, copy=False)
                        if d is None:
                            break  # server stopped
                        if (d.epoch != epoch
                                or len(d.versions) != len(versions)):
                            rebuild_layout(len(d.versions), d.epoch)
                            if not d.full:
                                # Paranoia: an epoch change must arrive
                                # as a full snapshot; if it somehow did
                                # not, re-pull from scratch.
                                d = client.pull_delta(
                                    (-1,) * len(d.versions), copy=False)
                                if d is None:
                                    break
                        for j, region in zip(d.shards, d.regions):
                            wire_host[row_start[j]:
                                      row_start[j]
                                      + region.shape[0]] = region
                        versions = d.versions
                        # jnp.array COPIES (asarray may alias on CPU,
                        # and the resident buffer mutates in place next
                        # pull).
                        wire_p = jnp.array(wire_host)
                        WIRE.h2d_bytes += wire_host.nbytes
                    else:
                        wire_np = client.pull_packed()
                        if wire_np is None:
                            break  # server stopped
                        if wire_np.shape[0] != layout.total_rows:
                            # Live reshard changed the wire layout.  A
                            # plain pull carries no arity, so probe it
                            # with a deliberately-mismatched delta pull:
                            # the full-fallback reply's version vector
                            # length IS the new arity, and it carries
                            # the new epoch.
                            probe = client.pull_delta((-1,))
                            if probe is None:
                                break
                            rebuild_layout(len(probe.versions),
                                           probe.epoch)
                            wire_np = client.pull_packed()
                            if wire_np is None:
                                break
                        wire_p = jnp.asarray(wire_np)
                        WIRE.h2d_bytes += wire_np.nbytes
                    WIRE.pulls += 1
                    batch = {k: jnp.asarray(v)
                             for k, v in next(stream).items()}
                    t_tr = tracer.now() if tracer is not None else 0.0
                    t0 = time.monotonic()
                    wire_g, loss = packed_step(wire_p, wire_g, batch)
                    loss = float(jax.block_until_ready(loss))
                    compute = time.monotonic() - t0
                    if tracer is not None:
                        tracer.span("compute_step", t_tr,
                                    worker=worker_id, clock=it,
                                    args={"loss": loss})
                    if slowdown > 1.0:
                        # The sleep IS the emulated slower device: its
                        # own span, which summarize counts as compute.
                        t_sleep = tracer.now() if tracer is not None \
                            else 0.0
                        time.sleep(compute * (slowdown - 1.0))
                        if tracer is not None:
                            tracer.span("straggler_sleep", t_sleep,
                                        worker=worker_id, clock=it)
                    client.record_loss(it, loss)
                    if not client.push_packed(np.asarray(wire_g),
                                              clock=it):
                        done += 1
                        break  # released with a STOP: training is over
                except FrameError as e:
                    if "resync" not in str(e):
                        raise
                    # Retryable bounce: the server could not place this
                    # frame under any layout it still knows (e.g. a
                    # failed-over server restored to a plan that
                    # predates our epoch).  Retry the iteration — the
                    # pull at the top full-resyncs and rebuilds the
                    # local layout first.
                    continue
                except (TransportClosed, OSError):
                    # The server died under us.  With a reconnect
                    # budget: back off, rebuild the channel, re-HELLO
                    # (idempotent — the seat is re-acquired, never
                    # duplicated), and RETRY this same iteration.  The
                    # kept `versions` vector is now ahead of the
                    # restored server's, so the next pull_delta
                    # dominance check forces a full resync; a push that
                    # died mid-gate is re-sent (duplicate-apply is
                    # ordinary async-SGD noise, loss is never lost
                    # silently).
                    if reconnect_policy is None:
                        raise
                    client.reconnect(reconnect_policy, seed=worker_id)
                    continue
                done += 1
                it += 1
                if it % max(1, task.get("trace_flush_every", 32)) == 0:
                    flush_trace()
        finally:
            flush_trace()
            client.bye()
            client.close()
            if spill_fh is not None:
                spill_fh.close()
        queue.put(WorkerResult(worker_id, done, platform=platform))
    except BaseException:
        queue.put(WorkerResult(worker_id, done,
                               error=traceback.format_exc(),
                               platform=platform))
        raise


class ProcessWorkerPool:
    """Spawn/join N transport workers with per-worker slowdown factors."""

    def __init__(self, address, task: WorkerTask, n_workers: int, *,
                 slowdowns: Optional[Sequence[float]] = None,
                 mp_context: str = "spawn"):
        if slowdowns is not None and len(slowdowns) != n_workers:
            raise ValueError(f"{len(slowdowns)} slowdown factors for "
                             f"{n_workers} workers")
        self.address = address
        self.task = task
        self.n_workers = n_workers
        self.slowdowns = list(slowdowns or [1.0] * n_workers)
        self._ctx = multiprocessing.get_context(mp_context)
        self._queue = self._ctx.Queue()
        self.procs: List[multiprocessing.Process] = []

    def start(self) -> None:
        task = self.task.to_dict()
        for w in range(self.n_workers):
            self.procs.append(self._spawn(w, task))

    def _spawn(self, w: int, task: Dict[str, Any]):
        p = self._ctx.Process(
            target=_worker_main,
            args=(task, self.address, w, self.slowdowns[w], self._queue),
            name=f"ps-proc-worker-{w}", daemon=True)
        p.start()
        return p

    @staticmethod
    def _respawn_task(task: Dict[str, Any]) -> Dict[str, Any]:
        """The task a replacement worker runs: identical, minus the
        self-kill fault (a respawned worker re-killing itself at the
        same round would churn forever)."""
        clean = dict(task)
        fp = dict(clean.get("fault_plan") or {})
        if fp:
            fp["kill_worker"] = -1
            fp["kill_worker_round"] = -1
            clean["fault_plan"] = fp
        return clean

    def join(self, timeout: float = 900.0, *, endpoint=None,
             respawn: int = 0) -> List[WorkerResult]:
        """Join all workers; reap stragglers; surface per-worker results.

        ``endpoint`` (a ``PSServerEndpoint``) gets ``on_disconnect`` for
        every abnormal exit — transports without connection semantics
        (shmem) cannot detect a dead peer themselves, and a corpse must
        not keep its seat in the barrier group.

        ``respawn`` is the elastic-fleet budget: up to that many
        abnormally-dead workers are replaced by fresh processes running
        the same task (its barrier seat was freed by ``on_disconnect``
        first, and the replacement's HELLO re-acquires it — exactly
        once).  The replacement restarts its local loop from iteration
        0: worker iterations are interchangeable SGD contributions, so
        elasticity costs repeated work, never corrupted state.
        """
        deadline = time.monotonic() + timeout
        # Poll instead of a blocking per-process join: a worker that
        # dies abnormally must release its barrier seat IMMEDIATELY
        # (endpoint.on_disconnect), or gate-blocked survivors would
        # wait on the corpse for the rest of the timeout.  tcp detects
        # this by EOF on its own; shmem has no connection, so this loop
        # is the only death detector it gets.
        reported = set()
        respawn_left = int(respawn)
        respawn_task = self._respawn_task(self.task.to_dict())
        self.respawned: List[int] = []
        while time.monotonic() < deadline:
            alive = False
            for w, p in enumerate(self.procs):
                if p.is_alive():
                    alive = True
                elif p.exitcode not in (0, None) and w not in reported:
                    if endpoint is not None:
                        endpoint.on_disconnect(w)
                    reported.add(w)
                    if respawn_left > 0:
                        respawn_left -= 1
                        self.procs[w] = self._spawn(w, respawn_task)
                        self.respawned.append(w)
                        reported.discard(w)
                        alive = True
            if not alive:
                break
            time.sleep(0.05)
        by_worker: Dict[int, WorkerResult] = {}
        while not self._queue.empty():
            r = self._queue.get_nowait()
            by_worker[r.worker_id] = r
        results = []
        for w, p in enumerate(self.procs):
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            r = by_worker.get(w) or WorkerResult(w, 0, error="no result "
                                                 "(killed or timed out)")
            r.exitcode = p.exitcode
            if (r.error or p.exitcode not in (0, None)) \
                    and endpoint is not None and w not in reported:
                endpoint.on_disconnect(w)
            results.append(r)
        return results

    def terminate(self) -> None:
        for p in self.procs:
            if p.is_alive():
                p.terminate()
        for p in self.procs:
            p.join(timeout=5.0)

    def alive(self) -> List[int]:
        return [w for w, p in enumerate(self.procs) if p.is_alive()]


def raise_on_failure(results: Sequence[WorkerResult]) -> None:
    failed = [r for r in results if r.error]
    if failed:
        msgs = "\n".join(f"-- worker {r.worker_id} "
                         f"(exit {r.exitcode}) --\n{r.error}"
                         for r in failed)
        raise RuntimeError(f"{len(failed)} worker process(es) failed:\n"
                           f"{msgs}")
