"""The serving engine: decode requests against the live resident buffer.

Three layers, composed the same way for process replicas (transport)
and thread replicas (in-heap):

  * ``Decoder`` — greedy continuation over the packed wire buffer.
    All jit objects are built ONCE per replica (fixed ``max_batch`` /
    ``prompt_len`` / ``max_new`` shapes, short batches padded up), so
    after the first batch every decode is compile-free — the seed-era
    driver re-jitted per call and paid tracing on every request.
  * ``ReplicaWorker`` — the serve loop: take a batch from the
    ``BatchQueue``, hold it at the ``wait_fresh`` admission gate until
    the resident buffer is within ``serve.staleness_bound`` of the
    server, snapshot buffer+version atomically, decode, complete each
    request with its latency / admitted staleness / served version.
  * ``ReplicaPool`` / ``_replica_main`` — spawn-and-join plumbing that
    mirrors ``launch.proc_pool``: replica ids start at
    ``n_workers`` (their transport slots sit after the trainers'), a
    ``ReplicaTask`` crosses the spawn boundary, weights never do.

Replicas drive themselves closed-loop: each generates its own Markov
prompts (deterministic in ``(data_seed, replica_id, request)``) and
scores the legal-successor fraction of what it decoded — the same
language-quality probe the training e2e tests use, now measured on
parameters that are mutating underneath the decoder.
"""

from __future__ import annotations

import dataclasses
import multiprocessing
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.launch.runtime import pin_child_platform
from repro.obs.trace import TRACE
from repro.serve.batching import BatchQueue, DecodeRequest
from repro.serve.replica import ParamSubscriber, Refresher
from repro.wireformat import WIRE_LANES


class Decoder:
    """Greedy decode over a packed wire buffer, jitted once.

    ``decode(wire_host, prompts)`` unpacks the buffer into the model
    tree and continues every prompt by ``max_new`` greedy tokens.
    Shapes are pinned at construction: prompts are ``(max_batch,
    prompt_len)`` (short batches padded by repeating the last row) and
    every jit call sees identical shapes, so compilation happens
    exactly once per replica lifetime.
    """

    def __init__(self, cfg, plan, *, prompt_len: int, max_new: int,
                 max_batch: int):
        import jax
        import jax.numpy as jnp

        from repro.models import registry, transformer

        if cfg.family == "audio":
            raise ValueError(
                "audio family serving is not supported: its decode "
                "path needs encoder frames, not token prompts")
        self.cfg = cfg
        self.plan = plan
        self.rows = plan.wire_layout().total_rows
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self.max_batch = int(max_batch)
        self._jnp = jnp
        fam = registry.family(cfg)
        total = self.prompt_len + self.max_new

        self._unpack = jax.jit(lambda w: plan.unpack(w))
        self._recurrent = cfg.family not in ("dense", "moe", "vlm")
        if not self._recurrent:
            def _prefill(p, toks):
                logits, cache = transformer.forward_prefill(cfg, p, toks)
                cache = {k: jnp.pad(
                    v, ((0, 0), (0, 0), (0, total - v.shape[2]),
                        (0, 0), (0, 0)))
                    for k, v in cache.items()}
                return logits[:, -1], cache
            self._prefill = jax.jit(_prefill)
        else:
            self._init_state = lambda b: fam.init_state(cfg, b, total)
        self._step = jax.jit(
            lambda p, t, c, i: fam.decode_fn(cfg, p, t, c, i))

    def rebuilt(self, n_shards: int) -> "Decoder":
        """A fresh decoder for the same model at a new shard arity —
        the serve loop swaps to this when a live reshard changes the
        resident buffer's wire layout.  Only ``_unpack`` genuinely
        re-traces; the prefill/step jits hit the compile cache."""
        return Decoder(self.cfg, self.plan.rebuild(n_shards),
                       prompt_len=self.prompt_len, max_new=self.max_new,
                       max_batch=self.max_batch)

    def warmup(self) -> None:
        """Compile every jit against a zeros buffer BEFORE the serve
        loop opens: request latency then measures decode, not trace
        time (the compile would otherwise land on the first batch's
        p99)."""
        layout = self.plan.wire_layout()
        wire = np.zeros((layout.total_rows, WIRE_LANES), layout.dtype)
        prompts = np.zeros((self.max_batch, self.prompt_len), np.int32)
        self.decode(wire, prompts)

    def decode(self, wire_host: np.ndarray,
               prompts: np.ndarray) -> np.ndarray:
        """(b, prompt_len) int32 prompts -> (b, max_new) greedy ids."""
        jnp = self._jnp
        b = prompts.shape[0]
        if prompts.shape != (b, self.prompt_len) or b > self.max_batch:
            raise ValueError(
                f"prompts {prompts.shape} do not fit this decoder "
                f"(<= {self.max_batch} rows of {self.prompt_len})")
        if b < self.max_batch:  # pad: jit shapes stay pinned
            pad = np.repeat(prompts[-1:], self.max_batch - b, axis=0)
            prompts = np.concatenate([prompts, pad], axis=0)
        toks = jnp.asarray(prompts, jnp.int32)
        # jnp.array COPIES — the resident buffer mutates under the
        # refresher, and on CPU asarray may alias host memory.
        params = self._unpack(jnp.array(wire_host))

        if not self._recurrent:
            last, cache = self._prefill(params, toks)
            pos = self.prompt_len
        else:
            cache = self._init_state(self.max_batch)
            last = None
            for i in range(self.prompt_len):
                last, cache = self._step(params, toks[:, i:i + 1], cache,
                                         jnp.int32(i))
                last = last[:, -1]
            pos = self.prompt_len
        next_tok = jnp.argmax(last, axis=-1)[:, None]
        out = [next_tok]
        for j in range(self.max_new - 1):
            logits, cache = self._step(params, next_tok, cache,
                                       jnp.int32(pos + j))
            next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            out.append(next_tok)
        return np.asarray(jnp.concatenate(out, axis=1))[:b]


@dataclasses.dataclass
class ReplicaResult:
    """What one replica hands back when its serve loop drains."""

    replica_id: int
    served: int = 0                 # requests completed
    batches: int = 0                # decode calls
    violations: int = 0             # admissions with staleness > bound
    blocks: int = 0                 # admission-gate stalls
    refreshes: int = 0              # delta pulls that landed
    full_refreshes: int = 0         # of which carried the full snapshot
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    staleness_values: List[int] = dataclasses.field(default_factory=list)
    served_versions: List[int] = dataclasses.field(default_factory=list)
    legal_fraction: float = 0.0     # Markov-legal generated transitions
    span_s: float = 0.0             # first submit -> last completion
    error: Optional[str] = None
    exitcode: Optional[int] = None
    platform: str = ""              # the JAX backend the replica ran on


class ReplicaWorker:
    """The serve loop around one queue + one subscriber + one decoder."""

    def __init__(self, replica_id: int, subscriber: ParamSubscriber,
                 queue: BatchQueue, decoder: Decoder, *,
                 staleness_bound: int, batch_window_ms: float,
                 max_batch: int):
        self.replica_id = int(replica_id)
        self.subscriber = subscriber
        self.queue = queue
        self.decoder = decoder
        self.staleness_bound = int(staleness_bound)
        self.window_s = float(batch_window_ms) / 1e3
        self.max_batch = int(max_batch)

    def serve(self) -> ReplicaResult:
        res = ReplicaResult(self.replica_id)
        sub = self.subscriber
        t_start = time.perf_counter()
        while True:
            batch = self.queue.next_batch(self.max_batch, self.window_s)
            if batch is None:
                break
            # The admission gate: blocks until the resident buffer is
            # within bound (or the server stopped — frozen weights).
            staleness = sub.wait_fresh(self.staleness_bound)
            wire, version = sub.snapshot()
            for _ in range(4):  # bounded: re-snapshot if a reshard races
                if wire.shape[0] == self.decoder.rows:
                    break
                # Live reshard landed between batches: the resident
                # buffer is now in a new wire layout.  Re-derive the
                # decode plan at the subscriber's new arity; weights
                # occupy the same canonical element space, so the
                # rebuilt unpack yields the same parameter tree.
                self.decoder = self.decoder.rebuilt(len(sub.versions))
                wire, version = sub.snapshot()
            t0 = TRACE.now() if TRACE.enabled else 0.0
            prompts = np.stack([r.prompt for r in batch]).astype(np.int32)
            tokens = self.decoder.decode(wire, prompts)
            if TRACE.enabled:
                TRACE.span("decode_batch", t0, worker=self.replica_id,
                           args={"batch": len(batch),
                                 "staleness": staleness,
                                 "version": version})
            done_t = time.perf_counter()
            for i, r in enumerate(batch):
                r.tokens = tokens[i]
                r.latency_s = done_t - r.enqueue_t
                r.staleness = staleness
                r.version = version
                r.done.set()
                res.latencies_s.append(r.latency_s)
            res.served += len(batch)
            res.batches += 1
            res.staleness_values.append(staleness)
            res.served_versions.append(version)
            if staleness > self.staleness_bound:
                res.violations += 1  # the gate failed: count it loudly
        res.blocks = sub.blocks
        res.refreshes = sub.refreshes
        res.full_refreshes = sub.full_refreshes
        res.span_s = time.perf_counter() - t_start
        return res


def legal_fraction(chain, prompts: np.ndarray,
                   generated: np.ndarray) -> float:
    """Fraction of generated transitions that are legal successors in
    the Markov chain — 1.0 for a trained model, ~branching/vocab for
    random weights."""
    succ = [set(row) for row in np.asarray(chain.successors)]
    legal = total = 0
    for p_row, g_row in zip(prompts, generated):
        prev = int(p_row[-1])
        for tok in g_row:
            tok = int(tok)
            legal += tok in succ[prev]
            total += 1
            prev = tok
    return legal / max(1, total)


def drive_replica(worker: ReplicaWorker, chain, *, requests: int,
                  prompt_len: int, pace_s: float = 0.0,
                  start_at_version: int = 0) -> ReplicaResult:
    """Run one replica closed-loop: a producer thread submits
    ``requests`` deterministic Markov prompts (lightly paced so the
    linger window sees arrivals, not one pre-filled queue), the serve
    loop drains them, and the result is scored for language legality.

    ``start_at_version`` holds the request stream back until the
    server has applied that many updates (or stopped) — how a run
    guarantees serving genuinely overlaps training instead of draining
    against the initial weights while the trainers are still
    compiling."""
    queue = worker.queue
    rid = worker.replica_id
    sub = worker.subscriber
    while sub.server_version < start_at_version and not sub.stopped:
        sub.staleness()  # refreshes the live view on in-heap subs
        time.sleep(0.02)
    reqs: List[DecodeRequest] = []

    def produce() -> None:
        for i in range(requests):
            row = chain.sample_rows(i, np.array([rid]))[0]
            r = DecodeRequest(request_id=i,
                              prompt=row[:prompt_len].astype(np.int32),
                              enqueue_t=time.perf_counter())
            reqs.append(r)
            queue.submit(r)
            if pace_s > 0:
                time.sleep(pace_s)
        queue.close()

    producer = threading.Thread(target=produce, daemon=True,
                                name=f"replica-driver-{rid}")
    producer.start()
    result = worker.serve()
    producer.join(timeout=30.0)
    done = [r for r in reqs if r.tokens is not None]
    if done:
        result.legal_fraction = legal_fraction(
            chain,
            np.stack([r.prompt for r in done]),
            np.stack([r.tokens for r in done]))
    return result


# -- spawn plumbing (mirrors launch.proc_pool) ---------------------------

@dataclasses.dataclass(frozen=True)
class ReplicaTask:
    """Everything a spawned replica needs; picklable and small —
    weights arrive over the transport, never the spawn boundary."""

    arch: str
    n_shards: int
    smoke: bool = True
    kernels: str = "auto"
    compress: str = "none"
    requests: int = 32
    request_every_ms: float = 0.0
    start_at_version: int = 0
    prompt_len: int = 16
    max_new: int = 8
    max_batch: int = 8
    batch_window_ms: float = 2.0
    staleness_bound: int = 4
    refresh_every_s: float = 0.05
    data_seed: int = 0
    trace: bool = False
    trace_spill: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_spec(cls, spec, *, trace_spill: str = "") -> "ReplicaTask":
        return cls(arch=spec.model.arch,
                   n_shards=max(1, spec.ps.shards),
                   smoke=spec.model.smoke,
                   kernels=spec.model.kernels,
                   compress=("int8" if spec.wire.compression == "int8"
                             else "none"),
                   requests=spec.serve.requests,
                   request_every_ms=spec.serve.request_every_ms,
                   start_at_version=spec.serve.start_at_version,
                   prompt_len=spec.serve.prompt_len,
                   max_new=spec.serve.max_new,
                   max_batch=spec.serve.max_batch,
                   batch_window_ms=spec.serve.batch_window_ms,
                   staleness_bound=spec.serve.staleness_bound,
                   refresh_every_s=spec.serve.refresh_every_s,
                   data_seed=spec.data.seed,
                   trace=bool(getattr(spec, "obs", None)
                              and spec.obs.trace),
                   trace_spill=trace_spill)


def _replica_main(task: Dict[str, Any], address, replica_id: int,
                  queue) -> None:
    """Entry point of one spawned serving replica process."""
    result = ReplicaResult(replica_id)
    try:
        pin_child_platform()
        import jax

        result.platform = jax.default_backend()

        from repro.configs import get_config, get_smoke_config
        from repro.data.synthetic import DataConfig, MarkovLM
        from repro.models import registry
        from repro.ps.sharded.plan import build_shard_plan
        from repro.serve.replica import TransportSubscription
        from repro.transport import connect

        cfg = (get_smoke_config(task["arch"]) if task["smoke"]
               else get_config(task["arch"]))
        if task.get("kernels", "auto") != cfg.kernels:
            cfg = dataclasses.replace(cfg, kernels=task["kernels"])
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        plan = build_shard_plan(params, task["n_shards"])
        layout = plan.wire_layout()
        del params  # the live weights come over the wire

        tracer = spill_fh = None
        if task.get("trace"):
            from repro.obs.trace import TRACE as tracer
            tracer.enable(source=f"w{replica_id}")
            if task.get("trace_spill"):
                os.makedirs(task["trace_spill"], exist_ok=True)
                spill_fh = open(os.path.join(task["trace_spill"],
                                             f"w{replica_id}.jsonl"),
                                "a", encoding="utf-8")

        client = connect(address, replica_id, compress=task["compress"])
        sub = TransportSubscription(client, task["n_shards"])
        if sub.rows != layout.total_rows:
            raise ValueError(
                f"server wire layout has {sub.rows} rows, local plan "
                f"derives {layout.total_rows} — replica task out of "
                "sync with server")
        subscriber = ParamSubscriber(sub, layout, replica_id=replica_id)
        refresher = Refresher(subscriber, task["refresh_every_s"])
        refresher.start()

        decoder = Decoder(cfg, plan, prompt_len=task["prompt_len"],
                          max_new=task["max_new"],
                          max_batch=task["max_batch"])
        decoder.warmup()  # compile before the first real request
        chain = MarkovLM(DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=task["prompt_len"] + task["max_new"],
            global_batch=1,
            seed=task["data_seed"] + 1000 + replica_id))
        worker = ReplicaWorker(
            replica_id, subscriber, BatchQueue(), decoder,
            staleness_bound=task["staleness_bound"],
            batch_window_ms=task["batch_window_ms"],
            max_batch=task["max_batch"])
        try:
            result = drive_replica(
                worker, chain, requests=task["requests"],
                prompt_len=task["prompt_len"],
                pace_s=task.get("request_every_ms", 0.0) / 1e3,
                start_at_version=task.get("start_at_version", 0))
            result.platform = jax.default_backend()
        finally:
            refresher.stop()
            if tracer is not None:
                events = tracer.drain()
                if events and spill_fh is not None:
                    import json
                    for e in events:
                        spill_fh.write(json.dumps(e,
                                                  separators=(",", ":")))
                        spill_fh.write("\n")
                    spill_fh.flush()
                if events:
                    try:
                        client.send_trace(events)
                    except Exception:
                        pass  # server gone — the spill still has them
            sub.close()
            if spill_fh is not None:
                spill_fh.close()
        queue.put(result)
    except BaseException:
        result.error = traceback.format_exc()
        queue.put(result)
        raise


class ReplicaPool:
    """Spawn/join R serving replicas on transport slots starting at
    ``first_id`` (= the trainer count: workers take 0..W-1, replicas
    W..W+R-1 — one shmem segment / tcp connection each)."""

    def __init__(self, address, task: ReplicaTask, n_replicas: int, *,
                 first_id: int, mp_context: str = "spawn"):
        self.address = address
        self.task = task
        self.n_replicas = int(n_replicas)
        self.first_id = int(first_id)
        self._ctx = multiprocessing.get_context(mp_context)
        self._queue = self._ctx.Queue()
        self.procs: List[multiprocessing.Process] = []

    def start(self) -> None:
        task = self.task.to_dict()
        for i in range(self.n_replicas):
            rid = self.first_id + i
            p = self._ctx.Process(
                target=_replica_main,
                args=(task, self.address, rid, self._queue),
                name=f"ps-serve-replica-{rid}", daemon=True)
            p.start()
            self.procs.append(p)

    def join(self, timeout: float = 900.0, *,
             endpoint=None) -> List[ReplicaResult]:
        deadline = time.monotonic() + timeout
        reported = set()
        while time.monotonic() < deadline:
            alive = False
            for i, p in enumerate(self.procs):
                rid = self.first_id + i
                if p.is_alive():
                    alive = True
                elif p.exitcode not in (0, None) and rid not in reported:
                    if endpoint is not None:
                        endpoint.on_disconnect(rid)  # unsubscribe only
                    reported.add(rid)
            if not alive:
                break
            time.sleep(0.05)
        by_id: Dict[int, ReplicaResult] = {}
        while not self._queue.empty():
            r = self._queue.get_nowait()
            by_id[r.replica_id] = r
        results = []
        for i, p in enumerate(self.procs):
            rid = self.first_id + i
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            r = by_id.get(rid) or ReplicaResult(
                rid, error="no result (killed or timed out)")
            r.exitcode = p.exitcode
            results.append(r)
        return results

    def terminate(self) -> None:
        for p in self.procs:
            if p.is_alive():
                p.terminate()
        for p in self.procs:
            p.join(timeout=5.0)


def raise_on_replica_failure(results: Sequence[ReplicaResult]) -> None:
    failed = [r for r in results if r.error]
    if failed:
        msgs = "\n".join(f"-- replica {r.replica_id} "
                         f"(exit {r.exitcode}) --\n{r.error}"
                         for r in failed)
        raise RuntimeError(f"{len(failed)} replica process(es) failed:\n"
                           f"{msgs}")


# -- aggregation ----------------------------------------------------------

def _percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return 0.0
    xs = sorted(values)
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def aggregate_serve(results: Sequence[ReplicaResult]) -> Dict[str, Any]:
    """One uniform serve-metrics dict from per-replica results — the
    shape ``session.metrics()['serve']``, the e2e tests, and
    ``benchmarks/serving.py`` all share."""
    results = [r for r in results if r is not None]
    lat = [s for r in results for s in r.latencies_s]
    stale = [s for r in results for s in r.staleness_values]
    versions = [v for r in results for v in r.served_versions]
    hist: Dict[str, int] = {}
    for s in stale:
        hist[str(s)] = hist.get(str(s), 0) + 1
    span = max((r.span_s for r in results), default=0.0)
    served = sum(r.served for r in results)
    return {
        "replicas": len(results),
        "requests": served,
        "batches": sum(r.batches for r in results),
        "violations": sum(r.violations for r in results),
        "blocks": sum(r.blocks for r in results),
        "refreshes": sum(r.refreshes for r in results),
        "full_refreshes": sum(r.full_refreshes for r in results),
        "requests_per_s": served / span if span > 0 else 0.0,
        "p50_ms": _percentile(lat, 0.50) * 1e3,
        "p99_ms": _percentile(lat, 0.99) * 1e3,
        "staleness_hist": hist,
        "staleness_max": max(stale, default=0),
        "version_min": min(versions, default=-1),
        "version_max": max(versions, default=-1),
        "legal_fraction": (sum(r.legal_fraction for r in results)
                           / len(results)) if results else 0.0,
        "platforms": sorted({r.platform for r in results if r.platform}),
    }


__all__ = [
    "Decoder",
    "ReplicaPool",
    "ReplicaResult",
    "ReplicaTask",
    "ReplicaWorker",
    "aggregate_serve",
    "drive_replica",
    "legal_fraction",
    "raise_on_replica_failure",
]
