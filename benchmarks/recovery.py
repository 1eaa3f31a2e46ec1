"""Recovery-path microbenchmark: what fault tolerance actually costs.

Measurements over a real sharded server (registry smoke model,
packed fused store), emitted as ``BENCH_recovery.json``:

  1. **snapshot** — per-shard pause imposed by an async snapshot: the
     time each shard's lock is HELD for capture (the window a push
     would queue behind), max and mean over ``--rounds`` snapshots,
     plus the end-to-end capture span.  The design contract is that
     the pause is per-shard and bounded — there is no global
     stop-the-world — so the gate checks ``pause_per_shard_us_max``.
  2. **resume** — wall time of ``restore_latest`` (read the newest
     on-disk snapshot, rebuild packed buffers + trackers + policy +
     metrics) into a fresh server: the dominant term in failover MTTR
     after process respawn.
  3. **reconnect** — wall time for ``--workers`` tcp clients to
     detect a dead listener, back off, and re-HELLO against a
     rebound one on the same port (mean tries per client recorded).
  4. **reshard** (``--reshard``) — live-migration cost S -> S' under
     concurrent pushes: per-shard pause (the copy-out lock hold, from
     ``reshard_shard`` spans), end-to-end migration wall time, and
     the zero-loss ledger (every parked push replayed, every sent
     push applied — the gate requires ``lost == 0``).

Run: ``PYTHONPATH=src python benchmarks/recovery.py [--smoke]
[--reshard]``.  Gate: ``perf_gate.py --recovery BENCH_recovery.json
[--recovery-previous <prior>]``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import tempfile
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_smoke_config
from repro.core.policies import make_policy_factory
from repro.ft.backoff import BackoffPolicy, retry
from repro.ft.snapshot import (
    ServerSnapshotter,
    restore_latest,
    snapshot_server,
)
from repro.models import registry
from repro.obs.trace import TRACE
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.transport import PSServerEndpoint, connect
from repro.transport.tcp import TcpTransport

SCHEMA = "recovery/v1"


def build_server(arch: str, n_shards: int, n_workers: int):
    params = registry.init_params(get_smoke_config(arch),
                                  jax.random.PRNGKey(0))
    return ShardedParameterServer(
        params,
        make_policy_factory("asp", n_workers=n_workers),
        lambda: ServerOptimizer(lr=0.05),
        n_workers, n_shards)


def bench_snapshot(server, rounds: int) -> dict:
    """Per-shard lock-hold pause + full capture span, from obs spans."""
    TRACE.enable(source="bench")
    pauses, spans = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        snapshot_server(server)
        spans.append(time.perf_counter() - t0)
        for e in TRACE.drain():
            if e.get("name") == "snapshot_shard":
                pauses.append(e["dur"])
    TRACE.disable()
    return {
        "rounds": rounds,
        "shards": len(server.shards),
        "pause_per_shard_us_max": max(pauses) * 1e6,
        "pause_per_shard_us_mean": statistics.fmean(pauses) * 1e6,
        "capture_span_ms_mean": statistics.fmean(spans) * 1e3,
    }


def bench_resume(server, arch: str, ckpt_dir: str) -> dict:
    """restore_latest wall time into a fresh server (disk -> packed)."""
    mgr = CheckpointManager(ckpt_dir, keep=2)
    ServerSnapshotter(server, mgr, every_s=3600.0).save_now()
    mgr.wait()
    fresh = build_server(arch, len(server.shards), 1)
    t0 = time.perf_counter()
    step = restore_latest(fresh, CheckpointManager(ckpt_dir, keep=2))
    restore_s = time.perf_counter() - t0
    return {"restore_ms": restore_s * 1e3, "ok": step == server.version}


def bench_reconnect(server, n_workers: int) -> dict:
    """Dead-listener detection + backoff + re-HELLO on a rebound port."""
    endpoint = PSServerEndpoint(server)
    t1 = TcpTransport("127.0.0.1", 0)
    t1.serve(endpoint)
    addr = t1.address()
    clients = [connect(addr, w) for w in range(n_workers)]
    for c in clients:
        c.hello()
    t1.shutdown()
    # Drop the dead channels so the server-side sockets leave
    # FIN_WAIT_2 (which blocks the rebind even with SO_REUSEADDR) for
    # TIME_WAIT (which does not).  In a real failover the workers do
    # this themselves the moment a request fails.
    for c in clients:
        try:
            c.channel.close()
        except OSError:
            pass

    def rebind():
        t = TcpTransport("127.0.0.1", addr[2])
        t.serve(endpoint)
        return t

    t2 = retry(rebind, BackoffPolicy(base_s=0.05, factor=2.0, max_s=0.5,
                                     max_tries=10))
    pol = BackoffPolicy(base_s=0.02, factor=2.0, max_s=0.2, max_tries=10)
    t0 = time.perf_counter()
    for c in clients:
        c.reconnect(pol, seed=c.worker_id)
    total_s = time.perf_counter() - t0
    for c in clients:
        c.close()
    t2.shutdown()
    return {"workers": n_workers, "total_reconnect_ms": total_s * 1e3,
            "mean_reconnects": statistics.fmean(
                c.reconnects for c in clients)}


def bench_reshard(arch: str, n_shards: int, to_shards: int,
                  n_workers: int, rounds: int) -> dict:
    """Live-migration cost under load: ``--workers`` threads keep
    pushing while the server reshards S -> S'.  Pushes racing the
    migration park-and-replay; the ledger must balance exactly."""
    import threading

    import jax.numpy as jnp
    import numpy as np

    from repro.perfcount import WIRE

    server = build_server(arch, n_shards, n_workers)
    g_tree = jax.tree_util.tree_map(
        lambda p: jnp.ones_like(p), server.params)
    wires: dict = {}
    sent = [0] * n_workers
    start = threading.Barrier(n_workers + 1)

    def pusher(w: int) -> None:
        start.wait()
        for _ in range(rounds):
            # re-grab the live plan each round: pushes packed under the
            # retired plan are inferred by shape and translated
            plan = server.plan
            wire = wires.get(id(plan))
            if wire is None:
                wires[id(plan)] = wire = plan.pack(g_tree)
            server.push_packed(w, wire)
            sent[w] += 1

    TRACE.enable(source="bench")
    WIRE.reset()
    threads = [threading.Thread(target=pusher, args=(w,), daemon=True)
               for w in range(n_workers)]
    for t in threads:
        t.start()
    start.wait()
    time.sleep(0.05)                 # let the push load build up
    t0 = time.perf_counter()
    assert server.reshard(to_shards)
    migration_s = time.perf_counter() - t0
    for t in threads:
        t.join(timeout=300.0)
    pauses = [e["dur"] for e in TRACE.drain()
              if e.get("name") == "reshard_shard"]
    TRACE.disable()
    ev = WIRE.snapshot()
    applied = server.metrics.total_pushes
    version_sum = server.version
    server.stop()
    return {
        "from_shards": n_shards,
        "to_shards": to_shards,
        "workers": n_workers,
        "migration_ms": migration_s * 1e3,
        "pause_per_shard_us_max": max(pauses) * 1e6,
        "pause_per_shard_us_mean": statistics.fmean(pauses) * 1e6,
        "parked": ev["reshard_parked"],
        "replayed": ev["reshard_replayed"],
        "translated": ev["reshard_translated"],
        # both ledgers must read zero: every parked region replayed,
        # every push a worker sent accounted in the server's metrics
        "lost": (ev["reshard_parked"] - ev["reshard_replayed"])
        + (sum(sent) - applied),
        "pushes_sent": sum(sent),
        "pushes_applied": applied,
        "version_sum": int(version_sum),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing: fewer snapshot rounds")
    ap.add_argument("--reshard", action="store_true",
                    help="also measure the live S -> S' migration "
                         "under concurrent pushes")
    ap.add_argument("--reshard-to", type=int, default=0,
                    help="target arity (default: shards + 2)")
    ap.add_argument("--out", default="BENCH_recovery.json")
    args = ap.parse_args()
    if args.smoke:
        args.rounds = 5

    server = build_server(args.arch, args.shards, args.workers)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        report = {
            "schema": SCHEMA,
            "arch": args.arch,
            "snapshot": bench_snapshot(server, args.rounds),
            "resume": bench_resume(server, args.arch, ckpt_dir),
            "reconnect": bench_reconnect(server, args.workers),
        }
    server.stop()
    if args.reshard:
        report["reshard"] = bench_reshard(
            args.arch, args.shards,
            args.reshard_to or args.shards + 2, args.workers,
            rounds=max(4, args.rounds))

    print(json.dumps(report, indent=2, sort_keys=True))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"\nwrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
