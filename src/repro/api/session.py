"""``build_session(spec) -> TrainingSession`` — the single wiring path.

Every way this repo trains (SPMD delayed-gradient pipeline, threaded
parameter server, process-isolated transport workers) is one *engine*
behind the same session surface:

    with build_session(spec) as session:      # start() on enter
        session.run(steps)                    # blocks until trained
        print(session.metrics())              # engine-uniform dict
                                              # close() on exit

Engines are registry-driven (``register_engine``) and selected from the
spec alone (``RunSpec.engine``); server construction is likewise
registry-driven (``register_server``).  All heavy imports (jax, the
model zoo, the transports) happen inside ``start()``/``run()`` so specs
can be built and validated anywhere — including spawned worker
processes and tooling that never trains.

Build-time overrides (keyword arguments to ``build_session``) inject
the pieces a spec cannot serialize: a custom parameter pytree, a custom
pytree step ``(params, batch) -> (grads, aux)`` (the session wraps it
in the packed step, ``repro.ps.step.packed_step``), per-worker batch
iterators, per-worker speed factors.
They exist for benchmarks and toy problems (``model.arch='custom'``);
ordinary runs need none of them.

``external_workers=True`` builds and serves the run's server side only
— the caller drives its own clients (benchmark harnesses); ``run()``
is then invalid.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Tuple

from repro._compat import api_managed
from repro.api.spec import CUSTOM_ARCH, RunSpec, SpecError

_ENGINES: Dict[str, type] = {}
_SERVER_BUILDERS: Dict[str, Callable] = {}


def register_engine(name: str):
    """Class decorator: make ``name`` a buildable session engine."""
    def deco(cls):
        cls.engine = name
        _ENGINES[name] = cls
        return cls
    return deco


def register_server(kind: str):
    """Register a server builder ``fn(spec, params) -> server`` for
    ``ps.kind == kind``."""
    def deco(fn):
        _SERVER_BUILDERS[kind] = fn
        return fn
    return deco


def build_session(spec, **overrides) -> "TrainingSession":
    """The one public entry point: a validated ``RunSpec`` (or a plain
    dict in its ``to_dict`` shape) in, an unstarted session out."""
    if isinstance(spec, dict):
        spec = RunSpec.from_dict(spec)
    if not isinstance(spec, RunSpec):
        raise SpecError(
            f"build_session takes a RunSpec or its dict form, got "
            f"{type(spec).__name__}")
    engine = spec.engine
    cls = _ENGINES.get(engine)
    if cls is None:  # unreachable unless a registry entry was removed
        raise SpecError(f"no session engine registered for {engine!r} "
                        f"(have {sorted(_ENGINES)})")
    return cls(spec, **overrides)


def build_server(spec: RunSpec, params=None):
    """Construct (only) the spec's parameter server — the registry hook
    the sessions use.  Public for tests; everything else should go
    through ``build_session``."""
    builder = _SERVER_BUILDERS.get(spec.ps.kind)
    if builder is None:
        raise SpecError(f"no server builder registered for "
                        f"ps.kind={spec.ps.kind!r} "
                        f"(have {sorted(_SERVER_BUILDERS)})")
    if params is None:
        params = _registry_params(spec)
    with api_managed():
        return builder(spec, params)


# ===================================================================
# session base
# ===================================================================
class TrainingSession:
    """Context-managed lifecycle over one training run.

    ``start()`` builds the heavy pieces (server, transport, jitted
    steps), ``run(steps)`` trains, ``metrics()`` reports an
    engine-uniform summary, ``close()`` releases gated workers and
    tears transports down.  Idempotent: ``start`` after start and
    ``close`` after close are no-ops.
    """

    engine = "base"
    OVERRIDES: frozenset = frozenset({"verbose"})

    def __init__(self, spec: RunSpec, **overrides):
        unknown = sorted(set(overrides) - self.OVERRIDES)
        if unknown:
            raise SpecError(
                f"unknown build_session override(s) {unknown} for the "
                f"{self.engine!r} engine; valid overrides: "
                f"{sorted(self.OVERRIDES)}")
        self.spec = spec
        self.verbose = bool(overrides.get("verbose", False))
        self._ov = overrides
        self._started = False
        self._closed = False

    # -- lifecycle ----------------------------------------------------
    def start(self) -> "TrainingSession":
        if not self._started:
            with api_managed():
                self._start()
            self._started = True
        return self

    def run(self, steps: int) -> Dict[str, Any]:
        """Train for ``steps`` global steps (PS engines divide them
        across workers, matching the historical CLI semantics).
        Returns ``metrics()``."""
        if self._closed:
            raise SpecError("session is closed")
        self.start()
        with api_managed():
            self._run(int(steps))
        return self.metrics()

    def metrics(self) -> Dict[str, Any]:
        raise NotImplementedError

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._started:
            self._close()

    def __enter__(self) -> "TrainingSession":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- engine hooks -------------------------------------------------
    def _start(self) -> None:
        raise NotImplementedError

    def _run(self, steps: int) -> None:
        raise NotImplementedError

    def _close(self) -> None:
        pass


# ===================================================================
# observability rig
# ===================================================================
class _ObsRig:
    """Per-session lifecycle for ``spec.obs``: enables the server-side
    recorder, runs the metrics sampler, merges worker flushes/spills
    into one ``TraceCollector``, exports on finish.

    All ``repro.obs`` imports are local so specs with tracing off never
    pay for the package.
    """

    def __init__(self, obs):
        from repro.obs import TraceCollector
        self.obs = obs
        self.collector = TraceCollector()
        self.sampler = None
        self.spill_dir = None
        self.summary = None
        self._done = False

    def start(self, metrics_fn=None) -> None:
        from repro.obs.trace import TRACE
        TRACE.enable(source="server")
        if self.obs.sample_every > 0 and metrics_fn is not None:
            from repro.obs import MetricsSampler
            self.sampler = MetricsSampler(TRACE, metrics_fn,
                                          self.obs.sample_every)
            self.sampler.start()

    def make_spill_dir(self) -> str:
        """Temp dir spawned workers spill their rings into (recovered
        on finish, so a killed worker's events still reach the trace)."""
        import tempfile
        if self.spill_dir is None:
            self.spill_dir = tempfile.mkdtemp(prefix="repro-obs-spill-")
        return self.spill_dir

    def finish(self) -> None:
        """Stop sampling, drain + merge every source, export, summarize.
        Idempotent — sessions call it from both ``_run`` and ``_close``."""
        if self._done:
            return
        self._done = True
        import shutil
        from repro.obs import summarize, write_chrome_trace, write_jsonl
        from repro.obs.trace import TRACE
        if self.sampler is not None:
            self.sampler.stop()
        self.collector.ingest_local(TRACE, source="server")
        TRACE.disable()
        if self.spill_dir is not None:
            self.collector.ingest_spill_dir(self.spill_dir)
            shutil.rmtree(self.spill_dir, ignore_errors=True)
        events = self.collector.timeline()
        path = self.obs.trace_path
        if path:
            if path.endswith(".jsonl"):
                write_jsonl(events, path)
            else:
                write_chrome_trace(events, path)
        self.summary = summarize(events)


class _FtRig:
    """Per-session lifecycle for ``spec.ft`` on the PS engines: the
    checkpoint manager, the optional resume-before-serve, and the
    periodic ``ServerSnapshotter``.  All ``repro.ft`` imports are local
    so specs without fault tolerance never pay for the package."""

    def __init__(self, ft, server):
        from repro.checkpoint.manager import CheckpointManager
        from repro.ft.snapshot import ServerSnapshotter, restore_latest
        self.manager = CheckpointManager(ft.dir, keep=ft.keep)
        # Resume BEFORE anything serves: endpoint pull caches are keyed
        # by version, and a restore moves versions backwards.
        self.resumed_step = (restore_latest(server, self.manager)
                             if ft.resume else None)
        self.snapshotter = (
            ServerSnapshotter(server, self.manager,
                              ft.snapshot_every_s).start()
            if ft.snapshot_every_s > 0 else None)
        self._done = False

    def finish(self) -> None:
        """Final snapshot + writer flush; surfaces any async-save
        failure the snapshotter thread parked.  Idempotent."""
        if self._done:
            return
        self._done = True
        if self.snapshotter is not None:
            self.snapshotter.stop(final_save=True)
        self.manager.wait()

    def metrics(self) -> Dict[str, Any]:
        return {
            "resumed_step": self.resumed_step,
            "snapshots": (self.snapshotter.snapshots
                          if self.snapshotter else 0),
            "latest_step": self.manager.latest_step(),
        }


def _obs_snapshot_fn(server):
    """Sampler callable for the PS engines: counters + the policy's
    current effective staleness bound (the DSSP threshold timeline)."""
    from repro.perfcount import snapshot_all

    def snap() -> Dict[str, Any]:
        m = server.metrics
        out = {
            "pushes": m.total_pushes,
            "applied": m.applied_updates,
            "version": server.version,
            "total_wait": round(m.total_wait, 6),
            "max_staleness": m.max_staleness,
            "credit_releases": m.credit_releases,
            "perfcount": snapshot_all(),
        }
        shard = server.shards[0]
        bound = shard.policy.effective_staleness_bound(shard.tracker)
        out["effective_threshold"] = (None if bound == float("inf")
                                      else float(bound))
        return out

    return snap


# ===================================================================
# server builders
# ===================================================================
def _server_optimizer_factory(spec: RunSpec):
    from repro.ps.sharded import ServerOptimizer
    opt = spec.optimizer
    damping = (False if opt.staleness_damping is None
               else opt.staleness_damping)
    momentum = opt.momentum if opt.name in (None, "sgd", "momentum") else 0.0
    return lambda: ServerOptimizer(lr=opt.lr, momentum=momentum,
                                   staleness_damping=damping)


def _wire_compression(spec: RunSpec):
    """The compression the server runs on the packed push (None when
    off, or when int8 rides the process-transport frames instead: they
    shrink the real wire bytes and dequantize on receipt, so the server
    must not quantize again)."""
    comp = spec.wire.compression
    if comp == "none" or (comp == "int8" and spec.transport.kind != "inproc"):
        return None
    return comp


@register_server("sharded")
def _build_sharded(spec: RunSpec, params):
    from repro.ps.sharded import ShardedParameterServer
    wait = spec.ps.coalesce_wait_ms
    return ShardedParameterServer(
        params, spec.sync.policy_factory(spec.ps.workers),
        _server_optimizer_factory(spec),
        spec.ps.workers, spec.ps.shards,
        gating=spec.ps.gating,
        wire_compression=_wire_compression(spec),
        topk_fraction=spec.wire.topk_fraction,
        coalesce=spec.ps.coalesce,
        coalesce_wait=None if wait is None else wait / 1e3)


# ===================================================================
# shared model plumbing (PS engines)
# ===================================================================
def _model_setup(spec: RunSpec):
    from repro.configs import get_config, get_smoke_config
    from repro.data.synthetic import DataConfig
    if spec.model.arch == CUSTOM_ARCH:
        raise SpecError(
            "model.arch='custom' needs build-time overrides (params=, "
            "step_fn=, batches=); name a registry architecture to run "
            "the model zoo")
    cfg = (get_smoke_config(spec.model.arch) if spec.model.smoke
           else get_config(spec.model.arch))
    if spec.model.kernels != cfg.kernels:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, kernels=spec.model.kernels)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size,
                          seq_len=spec.data.seq_len,
                          global_batch=spec.data.global_batch,
                          seed=spec.data.seed)
    return cfg, data_cfg


def _registry_params(spec: RunSpec):
    import jax
    from repro.models import registry
    cfg, _ = _model_setup(spec)
    return registry.init_params(cfg, jax.random.PRNGKey(0))


def _speed_factors(spec: RunSpec, override) -> List[float]:
    w = spec.ps.workers
    if override is not None:
        if len(override) != w:
            raise SpecError(f"{len(override)} speed factors for "
                            f"{w} workers")
        return list(override)
    return [spec.ps.straggler if i == w - 1 else 1.0 for i in range(w)]


def _default_loss_from_aux(aux) -> float:
    return float(aux["loss"])


# ===================================================================
# serving rig (both PS engines)
# ===================================================================
def _serve_threads(session) -> tuple:
    """Start ``spec.serve.replicas`` in-heap replica threads against a
    live server (the ps-threads engine's serve tier: replicas read the
    server directly, no transport).  Returns ``(threads, results)`` —
    join the threads, then read the results list."""
    spec = session.spec
    if spec.serve.replicas <= 0:
        return [], []
    import threading
    import traceback

    from repro.data.synthetic import DataConfig, MarkovLM
    from repro.serve import (
        BatchQueue,
        Decoder,
        DirectSubscription,
        ParamSubscriber,
        Refresher,
        ReplicaResult,
        ReplicaWorker,
        drive_replica,
    )
    cfg, _ = _model_setup(spec)
    plan = session.server.plan
    layout = plan.wire_layout()
    sv = spec.serve
    w = spec.ps.workers
    results: List = [None] * sv.replicas
    threads = []

    def run_one(i: int, rid: int) -> None:
        sub = DirectSubscription(session.server, rid)
        subscriber = ParamSubscriber(sub, layout, replica_id=rid)
        refresher = Refresher(subscriber, sv.refresh_every_s)
        refresher.start()
        try:
            decoder = Decoder(cfg, plan, prompt_len=sv.prompt_len,
                              max_new=sv.max_new, max_batch=sv.max_batch)
            decoder.warmup()  # compile before the first real request
            chain = MarkovLM(DataConfig(
                vocab_size=cfg.vocab_size,
                seq_len=sv.prompt_len + sv.max_new, global_batch=1,
                seed=spec.data.seed + 1000 + rid))
            worker = ReplicaWorker(
                rid, subscriber, BatchQueue(), decoder,
                staleness_bound=sv.staleness_bound,
                batch_window_ms=sv.batch_window_ms,
                max_batch=sv.max_batch)
            results[i] = drive_replica(
                worker, chain, requests=sv.requests,
                prompt_len=sv.prompt_len,
                pace_s=sv.request_every_ms / 1e3,
                start_at_version=sv.start_at_version)
        except Exception:
            results[i] = ReplicaResult(rid,
                                       error=traceback.format_exc())
        finally:
            refresher.stop()

    for i in range(sv.replicas):
        # Replica ids sit AFTER the trainers' (workers 0..W-1), same
        # slot convention as the transport engine.
        t = threading.Thread(target=run_one, args=(i, w + i),
                             daemon=True, name=f"serve-replica-{w + i}")
        t.start()
        threads.append(t)
    return threads, results


# ===================================================================
# engine: SPMD delayed-gradient pipeline
# ===================================================================
@register_engine("spmd")
class SpmdSession(TrainingSession):
    """The delayed-gradient emulation (``repro.launch.train.Trainer``):
    one process, the DSSP delay re-tuned per step by the Algorithm-2
    controller, gradient collective off the critical path."""

    OVERRIDES = frozenset({
        "verbose", "model_config", "data_config", "checkpoint_dir",
        "save_every", "resume", "collective_time_fn", "rules",
    })

    trainer = None
    resumed = False
    obs_rig = None

    def _start(self) -> None:
        from repro.data.synthetic import DataConfig
        from repro.launch.train import Trainer
        spec = self.spec
        if spec.obs.trace:
            self.obs_rig = _ObsRig(spec.obs)
            self.obs_rig.start()  # one process: no PS counters to sample
        cfg = self._ov.get("model_config")
        if cfg is None:
            cfg, data_cfg = _model_setup(spec)
        else:
            data_cfg = DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=spec.data.seq_len,
                                  global_batch=spec.data.global_batch,
                                  seed=spec.data.seed)
        data_cfg = self._ov.get("data_config") or data_cfg
        damping = spec.optimizer.staleness_damping
        self.trainer = Trainer(
            cfg, data_cfg, sync=spec.sync.mode,
            s_lower=spec.sync.s_lower, s_upper=spec.sync.s_upper,
            lr=spec.optimizer.lr, optimizer=spec.optimizer.name,
            compressor=spec.wire.compression,
            checkpoint_dir=self._ov.get("checkpoint_dir"),
            save_every=self._ov.get("save_every", 50),
            collective_time_fn=self._ov.get("collective_time_fn"),
            rules=self._ov.get("rules"),
            staleness_damping=True if damping is None else damping)
        if self._ov.get("resume"):
            self.resumed = self.trainer.resume()

    def _run(self, steps: int) -> None:
        self.trainer.train(steps, verbose=self.verbose)
        if self.obs_rig is not None:
            self.obs_rig.finish()

    def metrics(self) -> Dict[str, Any]:
        log = self.trainer.log if self.trainer else None
        losses = log.losses if log else []
        out = {
            "engine": self.engine,
            "steps": len(losses),
            "first_loss": losses[0] if losses else None,
            "final_loss": losses[-1] if losses else None,
            "mean_delay": (sum(log.delays) / len(log.delays)
                           if log and log.delays else 0.0),
        }
        if self.obs_rig is not None and self.obs_rig.summary is not None:
            out["obs"] = self.obs_rig.summary
        return out

    def _close(self) -> None:
        if self.obs_rig is not None:
            self.obs_rig.finish()


# ===================================================================
# engine: threaded parameter server
# ===================================================================
@register_engine("ps-threads")
class ThreadedPSSession(TrainingSession):
    """Worker threads pushing into an in-heap parameter server — the
    Algorithm-1 execution model with GIL-released jitted compute."""

    OVERRIDES = frozenset({
        "verbose", "params", "step_fn", "batches", "loss_from_aux",
        "speed_factors", "external_workers", "timeout",
    })

    server = None
    obs_rig = None
    ft_rig = None
    serve_results = None
    #: The shared jitted packed step ``(wire_p, wire_g, batch) ->
    #: (wire_g, aux)`` once ``run`` built it — for inspecting what
    #: compiled.
    worker_step = None
    #: JAX platform of each worker's step output, after ``run``.
    worker_platforms: Tuple[str, ...] = ()

    def _start(self) -> None:
        self.server = build_server(self.spec, self._ov.get("params"))
        if self.spec.ft.snapshots:
            self.ft_rig = _FtRig(self.spec.ft, self.server)
        if self.spec.obs.trace:
            self.obs_rig = _ObsRig(self.spec.obs)
            self.obs_rig.start(_obs_snapshot_fn(self.server))
        if self.verbose:
            print(self.server.plan.describe())

    def _run(self, steps: int) -> None:
        if self._ov.get("external_workers"):
            raise SpecError("this session was built with "
                            "external_workers=True — drive the server "
                            "yourself (run() has no workers to start)")
        from repro.ps.worker import PSWorker, run_cluster
        spec = self.spec
        w = spec.ps.workers
        iters = max(1, steps // w)
        speeds = _speed_factors(spec, self._ov.get("speed_factors"))
        step = self._worker_step()
        batches = self._batches_factory()
        loss_from_aux = self._ov.get("loss_from_aux",
                                     _default_loss_from_aux)
        workers = [
            PSWorker(i, self.server, step, batches(i), iters,
                     speed_factor=speeds[i],
                     delta_pull=spec.wire.delta_pull,
                     loss_from_aux=loss_from_aux)
            for i in range(w)]
        serve_threads, serve_results = _serve_threads(self)
        run_cluster(self.server, workers,
                    timeout=self._ov.get("timeout", 1200.0))
        self.worker_platforms = tuple(wk.platform for wk in workers)
        for t in serve_threads:
            t.join(timeout=self._ov.get("timeout", 1200.0))
        if serve_threads:
            self.serve_results = serve_results
            failed = [r for r in serve_results if r is not None and r.error]
            if failed:
                raise RuntimeError(
                    f"{len(failed)} serve replica(s) failed:\n"
                    + "\n".join(r.error for r in failed))
        if self.obs_rig is not None:
            self.obs_rig.finish()
        if self.verbose:
            m = self.server.metrics
            print(f"pushes={m.total_pushes} applied_updates="
                  f"{self.server.version} wait_s={m.total_wait:.2f} "
                  f"max_stale={m.max_staleness}")

    # -- worker construction ------------------------------------------
    def _worker_step(self):
        """The packed step every worker runs: the ``step_fn`` override
        (a pytree step) or the registry model's loss, around the
        server's wire layout.  Each worker donates its own gradient
        buffer into it."""
        from repro.ps.step import loss_step, packed_step
        tree_step = self._ov.get("step_fn")
        if tree_step is None:
            from repro.models import registry
            cfg, _ = _model_setup(self.spec)
            tree_step = loss_step(registry.loss_fn(cfg))
        self.worker_step = packed_step(self.server.plan, tree_step)
        return self.worker_step

    def _batches_factory(self):
        batches = self._ov.get("batches")
        if batches is not None:
            return batches
        import jax.numpy as jnp

        from repro.data.synthetic import batches as data_batches
        cfg, data_cfg = _model_setup(self.spec)

        def worker_batches(w: int) -> Iterator:
            wcfg = dataclasses.replace(data_cfg,
                                       seed=data_cfg.seed + 1 + w)
            for b in data_batches(cfg, wcfg):
                yield {k: jnp.asarray(v) for k, v in b.items()}

        return worker_batches

    # -- reporting ----------------------------------------------------
    def metrics(self) -> Dict[str, Any]:
        out = _ps_metrics(self.engine, self.server, self.obs_rig,
                          self.worker_platforms)
        if self.ft_rig is not None:
            out["ft"] = self.ft_rig.metrics()
        if self.serve_results is not None:
            from repro.serve import aggregate_serve
            out["serve"] = aggregate_serve(self.serve_results)
        return out

    def _close(self) -> None:
        if self.ft_rig is not None:
            self.ft_rig.finish()
        if self.server is not None:
            self.server.shutdown()
        if self.obs_rig is not None:
            self.obs_rig.finish()


# ===================================================================
# engine: process-isolated transport workers
# ===================================================================
@register_engine("ps-transport")
class TransportPSSession(TrainingSession):
    """Spawned worker processes pushing packed frames over a real wire
    (tcp / shmem / in-process loopback) into a ``PSServerEndpoint``."""

    OVERRIDES = frozenset({
        "verbose", "params", "external_workers", "speed_factors",
        "timeout",
    })

    server = None
    endpoint = None
    transport = None
    results = None
    obs_rig = None
    ft_rig = None
    serve_results = None
    #: JAX platform each spawned worker reported, after ``run``.
    worker_platforms: Tuple[str, ...] = ()

    def _start(self) -> None:
        from repro.transport import PSServerEndpoint, make_transport
        spec = self.spec
        self.server = build_server(spec, self._ov.get("params"))
        if spec.ft.snapshots:
            self.ft_rig = _FtRig(spec.ft, self.server)
        if spec.obs.trace:
            self.obs_rig = _ObsRig(spec.obs)
        self.endpoint = PSServerEndpoint(
            self.server,
            collector=self.obs_rig.collector if self.obs_rig else None)
        if self.obs_rig is not None:
            self.obs_rig.start(_obs_snapshot_fn(self.server))
        # Serving replicas take transport slots AFTER the trainers'
        # (shmem pre-allocates one segment per id; tcp ignores the
        # count) — workers 0..W-1, replicas W..W+R-1.
        self.transport = make_transport(
            spec.transport.kind,
            n_workers=spec.ps.workers + spec.serve.replicas,
            host=spec.transport.host, port=spec.transport.port)
        self.transport.serve(self.endpoint)

    def address(self):
        """The picklable transport address clients ``connect`` to."""
        self.start()
        return self.transport.address()

    def reshard(self, n_shards: int) -> bool:
        """Manual live-reshard trigger: migrate the running server's
        packed store to ``n_shards`` partitions WITHOUT stopping
        training (``repro.ft.reshard``).  Workers and replicas resync
        through the version-delta full-pull fallback on their next
        pull.  Returns False when the server is already at that arity."""
        self.start()
        return bool(self.server.reshard(int(n_shards)))

    def _run(self, steps: int) -> None:
        if self._ov.get("external_workers"):
            raise SpecError("this session was built with "
                            "external_workers=True — connect your own "
                            "clients to session.address()")
        if self.spec.transport.kind == "inproc":
            raise SpecError(
                "transport.endpoint=True over inproc is the in-process "
                "serialization baseline for external clients — spawned "
                "workers cannot reach an in-process address; use "
                "external_workers=True or transport.kind='tcp'/'shmem'")
        if self.spec.model.arch == CUSTOM_ARCH:
            raise SpecError(
                "transport workers rebuild the model from its config "
                "name — model.arch='custom' cannot cross the spawn "
                "boundary (pass a registry arch, or drive the endpoint "
                "with external_workers=True)")
        from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                            raise_on_failure)
        spec = self.spec
        w = spec.ps.workers
        iters = max(1, steps // w)
        task = WorkerTask.from_spec(
            spec, iters,
            trace_spill=(self.obs_rig.make_spill_dir()
                         if self.obs_rig else ""))
        slowdowns = _speed_factors(spec, self._ov.get("speed_factors"))
        pool = ProcessWorkerPool(self.transport.address(), task, w,
                                 slowdowns=slowdowns)
        rpool = None
        if spec.serve.replicas > 0:
            from repro.serve import ReplicaPool, ReplicaTask
            rtask = ReplicaTask.from_spec(
                spec, trace_spill=(self.obs_rig.make_spill_dir()
                                   if self.obs_rig else ""))
            rpool = ReplicaPool(self.transport.address(), rtask,
                                spec.serve.replicas, first_id=w)
        pool.start()
        if rpool is not None:
            rpool.start()
        trigger_stop = None
        if spec.ft.reshards:
            import threading
            trigger_stop = threading.Event()
            threading.Thread(
                target=_reshard_watch,
                args=(self.server, spec.ft, trigger_stop),
                name="reshard-trigger", daemon=True).start()
        try:
            self.results = pool.join(
                timeout=self._ov.get("timeout", 1200.0),
                endpoint=self.endpoint)
            if rpool is not None:
                # Replicas drain their own request load; join them
                # while the wire is still up (their last refreshes and
                # TRACE flushes ride it).
                self.serve_results = rpool.join(
                    timeout=self._ov.get("timeout", 1200.0),
                    endpoint=self.endpoint)
        finally:
            # Training is over either way: release gated workers and
            # tear the wire down before surfacing failures.
            if trigger_stop is not None:
                trigger_stop.set()
            self.close()
            pool.terminate()
            if rpool is not None:
                rpool.terminate()
        self.worker_platforms = tuple(r.platform for r in self.results)
        raise_on_failure(self.results)
        if rpool is not None:
            from repro.serve import raise_on_replica_failure
            raise_on_replica_failure(self.serve_results)
        if self.verbose:
            m = self.server.metrics
            done = sum(r.iterations_done for r in self.results)
            print(f"workers={w} ({spec.transport.kind}, platform="
                  f"{','.join(sorted(set(self.worker_platforms)))}) "
                  f"iterations={done} pushes={m.total_pushes} "
                  f"applied_updates={self.server.version} "
                  f"max_stale={m.max_staleness}")

    def metrics(self) -> Dict[str, Any]:
        out = _ps_metrics(self.engine, self.server, self.obs_rig,
                          self.worker_platforms)
        if self.results is not None:
            out["iterations_done"] = sum(r.iterations_done
                                         for r in self.results)
        if self.ft_rig is not None:
            out["ft"] = self.ft_rig.metrics()
        if self.serve_results is not None:
            from repro.serve import aggregate_serve
            out["serve"] = aggregate_serve(self.serve_results)
        return out

    def _close(self) -> None:
        if self.ft_rig is not None:
            self.ft_rig.finish()
        if self.server is not None:
            self.server.shutdown()
        if self.transport is not None:
            self.transport.shutdown()
        # After the transport is down: every in-flight TRACE frame has
        # either been dispatched into the collector or lost to the
        # spill files the rig is about to recover.
        if self.obs_rig is not None:
            self.obs_rig.finish()


def _reshard_watch(server, ft, stop) -> None:
    """Background live-reshard trigger for the in-parent transport
    session: fire at the manual push round (``ft.reshard_round``)
    and/or when one shard's applied-update growth exceeds
    ``ft.reshard_hot_factor`` x the uniform share (the hot-shard
    policy).  One-shot — the thread exits after triggering.  The
    ``repro.ft.server_proc`` process runs its own copy of this logic
    (plus the mid-migration kill hook) for out-of-process servers."""
    import time as _time
    last = server.shard_versions()
    while not stop.is_set() and not server.stopped:
        _time.sleep(0.02)
        if ft.reshard_round >= 0 \
                and server.metrics.total_pushes >= ft.reshard_round:
            server.reshard(ft.reshard_shards)
            return
        if ft.reshard_hot_factor > 0.0:
            cur = server.shard_versions()
            if len(cur) == len(last):
                deltas = [c - b for c, b in zip(cur, last)]
                total = sum(deltas)
                if total > 0 and max(deltas) > \
                        ft.reshard_hot_factor * (total / len(deltas)):
                    server.reshard(ft.reshard_shards)
                    return
            last = cur


def _ps_metrics(engine: str, server, obs_rig=None,
                worker_platforms=()) -> Dict[str, Any]:
    if server is None:
        return {"engine": engine}
    from repro.perfcount import snapshot_all
    m = server.metrics
    losses = [loss for _, _, loss in m.loss_trajectory]
    out = {
        "engine": engine,
        "pushes": m.total_pushes,
        "applied_updates": server.version,
        "max_staleness": m.max_staleness,
        "total_wait": m.total_wait,
        "wait_fraction": m.wait_fraction(),
        "credit_releases": m.credit_releases,
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "perfcount": snapshot_all(),
        # the JAX platforms the workers' steps ran on
        "worker_platforms": sorted({p for p in worker_platforms if p}),
    }
    if obs_rig is not None and obs_rig.summary is not None:
        out["obs"] = obs_rig.summary
    return out
