"""Fault tolerance (``repro.ft``): snapshots, failover, reconnect, chaos.

Unit layer: backoff schedules, checkpoint-manager crash hygiene,
snapshot/restore bitwise fidelity (including the cross-shard count
equalization that keeps post-failover DSSP gating deadlock-free), and
deterministic fault injection.

Process layer (the chaos tests CI's ``chaos`` job re-runs in
isolation): a worker SIGKILLed while the other is gated on it frees
its barrier seat and a respawned replacement re-acquires it exactly
once (tcp AND shmem); and the headline end-to-end — a 2-worker DSSP
run over tcp whose server is SIGKILLed mid-run, restarted on the same
port, resumes from the latest snapshot with both workers reconnected,
no duplicate seats, the loss trajectory intact across the failover,
and the per-shard snapshot pause bounded (asserted from obs spans).
"""

from __future__ import annotations

import glob
import json
import math
import os
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.policies import make_policy_factory
from repro.ft import (
    BackoffPolicy,
    FaultPlan,
    ServerProcess,
    retry,
)
from repro.ft.faults import FaultyChannel
from repro.ft.snapshot import (
    ServerSnapshotter,
    restore_latest,
    restore_server,
    snapshot_server,
)
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.transport import (
    PSServerEndpoint,
    TransportClosed,
    connect,
    make_transport,
)
from repro.transport.tcp import TcpTransport
from repro.wireformat import MSG_PULL, MSG_PUSH, WIRE_LANES

pytestmark = pytest.mark.filterwarnings("ignore::UserWarning")

ARCH = "xlstm-125m"  # registry arch the spawned chaos workers rebuild


# ---------------------------------------------------------------- helpers
def tiny_params():
    return {"w": jnp.ones((48, 32), jnp.float32),
            "b": jnp.zeros((17,), jnp.float32)}


def make_server(n_workers=1, n_shards=2, policy="asp", s_lower=0,
                s_upper=3, **pkw):
    # DSSP tests that push single-threaded need a slack s_lower: with a
    # tight bound the first push gates on a peer that never comes.
    return ShardedParameterServer(
        tiny_params(),
        make_policy_factory(policy, n_workers=n_workers, staleness=2,
                            s_lower=s_lower, s_upper=s_upper, **pkw),
        lambda: ServerOptimizer(lr=0.05),
        n_workers, n_shards)


def push_rounds(server, n, workers=(0,), seed=0):
    rng = np.random.RandomState(seed)
    rows = server.plan.wire_layout().total_rows
    for _ in range(n):
        for w in workers:
            g = rng.randn(rows, WIRE_LANES).astype(np.float32)
            server.push_packed(w, jnp.asarray(g))


def packed_state(server):
    return [(np.asarray(st._packed_p).tobytes(),
             np.asarray(st._packed_m).tobytes())
            for st in server.shards]


# ============================================================ backoff
class TestBackoff:
    def test_delays_deterministic_bounded_and_sized(self):
        pol = BackoffPolicy(base_s=0.1, factor=2.0, max_s=0.5, max_tries=6)
        a = list(pol.delays(seed=7))
        b = list(pol.delays(seed=7))
        assert a == b                       # reproducible chaos
        assert len(a) == pol.max_tries - 1  # one sleep between tries
        assert all(0.0 < d <= 0.5 * (1.0 + pol.jitter) for d in a)
        assert a != list(pol.delays(seed=8))

    def test_retry_returns_first_success(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("not yet")
            return "ok"

        pol = BackoffPolicy(base_s=0.001, factor=1.0, max_s=0.001,
                            max_tries=5)
        assert retry(fn, pol) == "ok"
        assert len(calls) == 3

    def test_retry_exhausts_schedule_and_reraises_last(self):
        calls = []

        def fn():
            calls.append(1)
            raise ConnectionRefusedError(f"attempt {len(calls)}")

        pol = BackoffPolicy(base_s=0.001, factor=1.0, max_s=0.001,
                            max_tries=4)
        with pytest.raises(ConnectionRefusedError, match="attempt 4"):
            retry(fn, pol)
        assert len(calls) == 4

    def test_retry_does_not_catch_foreign_errors(self):
        pol = BackoffPolicy(base_s=0.001, factor=1.0, max_s=0.001,
                            max_tries=3)
        with pytest.raises(ValueError):
            retry(lambda: (_ for _ in ()).throw(ValueError("x")), pol,
                  retry_on=(OSError,))


# ============================================================ checkpoints
class TestCheckpointManager:
    def test_async_write_failure_surfaces_on_next_call(self, tmp_path,
                                                       monkeypatch):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        boom = RuntimeError("disk full")

        def bad_save(*a, **k):
            raise boom

        monkeypatch.setattr(np, "save", bad_save)
        mgr.save(1, {"x": np.zeros(3)})     # async: returns immediately
        with pytest.raises(RuntimeError, match="disk full"):
            mgr.wait()
        # the parked error is consumed — the manager is usable again
        monkeypatch.undo()
        mgr.save(2, {"x": np.zeros(3)})
        mgr.wait()
        assert mgr.steps() == [2]

    def test_sync_write_failure_raises_at_call_site(self, tmp_path,
                                                    monkeypatch):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        monkeypatch.setattr(
            np, "save",
            lambda *a, **k: (_ for _ in ()).throw(OSError("nope")))
        with pytest.raises(OSError, match="nope"):
            mgr.save(1, {"x": np.zeros(3)})

    def test_tmp_gc_and_torn_snapshots_skipped(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        mgr.save(5, {"x": np.arange(4)}, {"tag": "good"})
        mgr.wait()
        # a crash mid-save leaves a .tmp_; a foreign writer may leave a
        # manifest-less step dir — neither may shadow the good snapshot
        os.makedirs(tmp_path / "step_000000009.tmp_")
        os.makedirs(tmp_path / "step_000000010")
        assert mgr.steps() == [5]
        step, tree, extras = mgr.restore_latest({"x": np.zeros(4, int)})
        assert step == 5 and extras["tag"] == "good"
        np.testing.assert_array_equal(tree["x"], np.arange(4))
        # a NEW manager (the restarted server) GCs the torn tmp dir
        CheckpointManager(str(tmp_path), keep=3)
        assert not (tmp_path / "step_000000009.tmp_").exists()


# ============================================================ snapshots
class TestSnapshotRestore:
    def test_roundtrip_bitwise_and_resume_stays_bitwise(self, tmp_path):
        """Restore is bitwise AND the restored server's next apply is
        bitwise-identical to the original's — resume at a snapshot
        boundary replays the same trajectory."""
        a = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        push_rounds(a, 3, workers=(0, 1))
        tree, extras = snapshot_server(a)

        b = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        restore_server(b, tree, extras)
        assert packed_state(a) == packed_state(b)
        assert a.shard_versions() == b.shard_versions()
        assert a.metrics.total_pushes == b.metrics.total_pushes
        for sa, sb in zip(a.shards, b.shards):
            assert sa.tracker.counts == sb.tracker.counts
            assert sa.tracker.credits == sb.tracker.credits

        push_rounds(a, 2, workers=(0, 1), seed=99)
        push_rounds(b, 2, workers=(0, 1), seed=99)
        assert packed_state(a) == packed_state(b)

    def test_restore_equalizes_crossshard_counts(self):
        """Regression for the post-failover DSSP hang: a snapshot can
        catch a push recorded on early shards but not late ones; the
        worker then RETRIES that push, and without equalization its
        early-shard counts run two ahead — two workers could block on
        each other across different shards' barriers forever."""
        a = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        push_rounds(a, 2, workers=(0, 1))
        tree, extras = snapshot_server(a)
        # simulate the mid-push capture: worker 0's interrupted push
        # made it onto shard 0's tracker only
        counts = extras["shards"][0]["tracker"]["counts"]
        counts["0"] = int(counts["0"]) + 1

        b = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        restore_server(b, tree, extras)
        for st in b.shards:
            assert st.tracker.counts == {0: 2, 1: 2}
            # table A is reset: the dead process's clock readings must
            # not feed the Algorithm-2 estimator
            assert all(math.isnan(x) for ts in st.tracker.table.values()
                       for x in ts)

    def test_restore_across_topology_reshards_then_installs(self):
        """A cross-arity restore is no longer an error: the target
        server live-reshards to the snapshot's arity FIRST (installing
        the migration map, so stale-epoch pushes keep translating),
        then installs shard-for-shard — bitwise."""
        a = make_server(n_shards=2)
        push_rounds(a, 2)
        tree, extras = snapshot_server(a)
        b = make_server(n_shards=3)
        restore_server(b, tree, extras)
        assert len(b.shards) == 2
        assert b.reshard_epoch == 1          # the reshard that aligned it
        assert packed_state(a) == packed_state(b)
        assert a.shard_versions() == b.shard_versions()

    def test_snapshotter_skips_unchanged_and_keeps_k(self, tmp_path):
        server = make_server()
        mgr = CheckpointManager(str(tmp_path), keep=2)
        snap = ServerSnapshotter(server, mgr, every_s=60.0)
        push_rounds(server, 1)
        assert snap.save_now() is True
        assert snap.save_now() is False      # nothing moved
        for seed in (1, 2, 3):
            push_rounds(server, 1, seed=seed)
            assert snap.save_now() is True
        mgr.wait()
        assert len(mgr.steps()) == 2         # keep-K GC ran

    def test_restore_latest_roundtrips_through_disk(self, tmp_path):
        a = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        push_rounds(a, 3, workers=(0, 1))
        mgr = CheckpointManager(str(tmp_path), keep=3)
        ServerSnapshotter(a, mgr, every_s=60.0).save_now()
        mgr.wait()

        b = make_server(n_workers=2, policy="dssp", s_lower=8,
                        s_upper=16)
        step = restore_latest(b, CheckpointManager(str(tmp_path), keep=3))
        assert step == a.version
        assert packed_state(a) == packed_state(b)

    def test_restore_latest_on_empty_dir_is_fresh_start(self, tmp_path):
        b = make_server()
        assert restore_latest(
            b, CheckpointManager(str(tmp_path), keep=3)) is None
        assert b.version == 0

# ============================================================ fault plans
class _CountingChannel:
    def __init__(self):
        self.requests = 0

    def request(self, data):
        self.requests += 1
        return b"ok"

    def close(self):
        pass


class TestFaultPlan:
    def test_roundtrip_and_unknown_keys_ignored(self):
        plan = FaultPlan(kill_server_round=10, drop_kind=MSG_PUSH,
                         drop_prob=0.25, seed=3)
        d = plan.to_dict()
        d["someday_field"] = 1
        assert FaultPlan.from_dict(d) == plan
        assert FaultPlan.from_dict(None) == FaultPlan()
        assert not FaultPlan().active

    def test_validation(self):
        with pytest.raises(ValueError):
            FaultPlan(drop_prob=1.5)
        with pytest.raises(ValueError):
            FaultPlan(delay_ms=-1.0)

    def test_worker_kill_due(self):
        plan = FaultPlan(kill_worker=1, kill_worker_round=3)
        assert plan.worker_kill_due(1, 3)
        assert not plan.worker_kill_due(0, 3)
        assert not plan.worker_kill_due(1, 2)
        assert not FaultPlan().worker_kill_due(0, 0)

    def test_drops_are_deterministic_and_kind_filtered(self):
        from repro.wireformat import Frame, encode_frame
        push = encode_frame(Frame(
            kind=MSG_PUSH,
            payload=np.zeros((8, WIRE_LANES), np.float32)))
        pull = encode_frame(Frame(kind=MSG_PULL))
        plan = FaultPlan(drop_kind=MSG_PUSH, drop_prob=0.5, seed=11)

        def outcomes():
            ch = FaultyChannel(_CountingChannel(), plan, worker_id=4)
            out = []
            for _ in range(32):
                try:
                    ch.request(push)
                    out.append("ok")
                except TransportClosed:
                    out.append("drop")
            return out, ch

        a, ch_a = outcomes()
        b, _ = outcomes()
        assert a == b                        # same plan+worker, same chaos
        assert "drop" in a and "ok" in a
        # non-matching kinds pass untouched (RNG not even consulted)
        before = ch_a.inner.requests
        for _ in range(8):
            ch_a.request(pull)
        assert ch_a.inner.requests == before + 8


# ============================================================ reconnect
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestReconnect:
    def test_tcp_connect_retries_until_server_binds(self):
        server = make_server()
        port = _free_port()
        address = ("tcp", "127.0.0.1", port)
        transports = []

        def bind_late():
            time.sleep(0.4)
            endpoint = PSServerEndpoint(server)
            t = TcpTransport("127.0.0.1", port)
            t.serve(endpoint)
            transports.append(t)

        threading.Thread(target=bind_late, daemon=True).start()
        client = connect(address, 0)         # retries with backoff
        try:
            assert client.hello() == server.plan.wire_layout().total_rows
        finally:
            client.close()
            time.sleep(0.05)
            transports[0].shutdown()

    def test_client_reconnect_reacquires_seat_exactly_once(self):
        server = make_server(n_workers=1)
        endpoint = PSServerEndpoint(server)
        port = _free_port()
        t1 = TcpTransport("127.0.0.1", port)
        t1.serve(endpoint)
        client = connect(("tcp", "127.0.0.1", port), 0)
        rows = client.hello()
        wire = client.pull_packed()
        assert wire is not None

        t1.shutdown()                        # the server machine dies
        with pytest.raises((TransportClosed, OSError)):
            for _ in range(4):               # first recv may drain a buffer
                client.pull_packed()
        # drop the dead channel so the server-side socket leaves
        # FIN_WAIT_2 (blocks rebind) for TIME_WAIT (does not); a real
        # worker's reconnect() does this before its first retry
        client.channel.close()

        def rebind():                        # failover on the same port
            t = TcpTransport("127.0.0.1", port)
            t.serve(endpoint)
            return t

        t2 = retry(rebind, BackoffPolicy(base_s=0.05, factor=2.0,
                                         max_s=0.5, max_tries=10))
        try:
            pol = BackoffPolicy(base_s=0.05, factor=2.0, max_s=0.4,
                                max_tries=8)
            assert client.reconnect(pol) == rows
            assert client.reconnects == 1
            # the seat exists exactly once on every shard
            for st in server.shards:
                assert st.tracker.workers == [0]
            # and the wire is live again end to end
            g = np.random.RandomState(0).randn(
                rows, WIRE_LANES).astype(np.float32)
            assert client.push_packed(g) is True
        finally:
            client.close()
            t2.shutdown()


# ============================================================ chaos (procs)
def _registry_server(n_workers, policy="bsp"):
    import jax

    from repro.configs import get_smoke_config
    from repro.models import registry

    params = registry.init_params(get_smoke_config(ARCH),
                                  jax.random.PRNGKey(0))
    return ShardedParameterServer(
        params, make_policy_factory(policy, n_workers=n_workers,
                                    s_lower=0, s_upper=3),
        lambda: ServerOptimizer(lr=0.05), n_workers, 2)


@pytest.mark.parametrize("kind", ["tcp", "shmem"])
def test_worker_killed_while_gated_seat_freed_and_respawned(kind):
    """Chaos: worker 1 SIGKILLs itself mid-run while worker 0 is gated
    on it (BSP barrier).  The corpse's seat is freed (worker 0 runs
    on), and the respawned replacement re-acquires the seat exactly
    once."""
    from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                        raise_on_failure)

    server = _registry_server(n_workers=2, policy="bsp")
    endpoint = PSServerEndpoint(server)
    transport = make_transport(kind, n_workers=2)
    transport.serve(endpoint)
    task = WorkerTask(
        arch=ARCH, n_shards=2, n_iterations=4,
        fault_plan=FaultPlan(kill_worker=1,
                             kill_worker_round=2).to_dict())
    pool = ProcessWorkerPool(transport.address(), task, 2)
    pool.start()
    try:
        results = pool.join(timeout=240.0, endpoint=endpoint, respawn=1)
        raise_on_failure(results)
        assert pool.respawned == [1]         # exactly one replacement
        assert [r.iterations_done for r in results] == [4, 4]
        # A duplicated seat would leave the BSP barrier waiting on a
        # phantom worker (the join above would time out); completing
        # proves the replacement re-acquired worker 1's seat exactly
        # once.  Clean BYEs then release every seat — none leak.
        for st in server.shards:
            assert st.tracker.workers == []
        assert server.metrics.pushes[0] == 4
        assert server.metrics.pushes[1] >= 4     # corpse's rounds + rerun
    finally:
        pool.terminate()
        server.stop()
        transport.shutdown()


def test_chaos_dssp_server_sigkill_resumes_and_recovers(tmp_path):
    """The headline end-to-end: 2-worker DSSP over tcp, server
    SIGKILLed at aggregate push round 10 by its own FaultPlan watchdog,
    restarted on the SAME port, resumes from the latest snapshot; both
    workers reconnect (no hang, no duplicate barrier seats), finish
    every iteration, the loss trajectory spans the failover, and the
    per-shard snapshot pause is bounded (from the spilled obs spans)."""
    from repro.api import RunSpec
    from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                        raise_on_failure)

    ckpt = tmp_path / "ckpt"
    spill = tmp_path / "spill"
    spec = RunSpec.from_dict({
        "model": {"arch": ARCH, "smoke": True},
        "ps": {"kind": "sharded", "shards": 2, "workers": 2,
               "apply": "fused"},
        "wire": {"format": "packed", "delta_pull": True},
        "sync": {"mode": "dssp"},
        "transport": {"kind": "tcp"},
        # The cadence must put a snapshot between the first pushes and
        # the kill; the jitted apply makes those pushes milliseconds apart.
        "ft": {"snapshot_every_s": 0.01, "dir": str(ckpt), "resume": True,
               "reconnect_tries": 10, "reconnect_base_s": 0.1,
               "reconnect_max_s": 2.0, "fault_kill_server_round": 10,
               "fault_seed": 7},
    })
    sp = ServerProcess(spec, trace_spill=str(spill))
    addr = sp.start()
    assert sp.resumed_step is None           # fresh run: nothing to resume
    pool = ProcessWorkerPool(addr, WorkerTask.from_spec(spec, 12), 2)
    pool.start()
    try:
        assert sp.wait_dead(180.0), "FaultPlan watchdog never fired"
        addr2 = sp.restart()
        assert addr2 == addr                 # same host:port across failover
        assert sp.resumed_step is not None and sp.resumed_step > 0
        results = pool.join(timeout=300.0)
        raise_on_failure(results)
        assert [r.iterations_done for r in results] == [12, 12]
    finally:
        pool.terminate()
        sp.stop()
        sp.kill()

    # -- post-mortem over the on-disk snapshots -----------------------
    mgr = CheckpointManager(str(ckpt), keep=spec.ft.keep)
    step = mgr.latest_step()
    assert step is not None and step >= sp.resumed_step
    # every captured state (including mid-run ones) holds each barrier
    # seat at most once — a duplicate seat after reconnect would also
    # have hung the join above
    for s in mgr.steps():
        with open(os.path.join(mgr._step_dir(s), "manifest.json")) as f:
            ex = json.load(f)["extras"]
        for shard in ex["shards"]:
            workers = shard["tracker"]["workers"]
            assert len(workers) == len(set(workers))
            assert set(workers) <= {0, 1}
    # the final (graceful-stop) snapshot: clean BYEs released every
    # seat, and both workers pushed their full run through the server.
    # Metrics are restored from the last pre-kill snapshot, so pushes
    # acked in the (snapshot, SIGKILL] window are legitimately absent —
    # the worker got its ack and never re-sends them.  The kill fires
    # once total_pushes reaches 10 and resumed_step is the version the
    # snapshot captured, so that window holds at most 10 - resumed_step
    # pushes (+ slack for watchdog-poll overshoot).  Conversely the one
    # in-flight push a worker DOES retry can be double-counted: +1.
    with open(os.path.join(mgr._step_dir(step), "manifest.json")) as f:
        extras = json.load(f)["extras"]
    for shard in extras["shards"]:
        assert shard["tracker"]["workers"] == []
    pushes = {int(w): c for w, c in extras["metrics"]["pushes"].items()}
    lost = max(0, 10 - sp.resumed_step) + 2
    assert set(pushes) == {0, 1}
    assert pushes[0] >= 12 - lost and pushes[1] >= 12 - lost
    assert pushes[0] <= 13 and pushes[1] <= 13
    assert sum(pushes.values()) >= 24 - lost
    losses = [p[2] for p in extras["metrics"]["loss_trajectory"]]
    assert len(losses) >= 12                 # spans both incarnations
    assert all(math.isfinite(x) for x in losses)
    assert min(losses[-4:]) <= losses[0] + 0.5   # training recovered

    # -- spilled obs spans survived the SIGKILL -----------------------
    events = []
    for p in glob.glob(str(spill / "*.jsonl")):
        with open(p) as f:
            events.extend(json.loads(line) for line in f)
    names = {e["name"] for e in events}
    assert {"snapshot", "snapshot_shard", "failover"} <= names
    failover = [e for e in events if e["name"] == "failover"]
    assert len(failover) == 1
    assert failover[0]["args"]["step"] == sp.resumed_step
    # per-shard pause = the snapshot's lock HOLD, bounded well below
    # the push path's own apply latency on this box
    pauses = [e["dur"] for e in events if e["name"] == "snapshot_shard"]
    assert pauses and max(pauses) < 0.5


def test_chaos_reshard_sigkill_mid_migration_resumes_untorn(tmp_path):
    """Reshard x failover: the server's own FaultPlan SIGKILLs it
    MID-MIGRATION (after old shards have been copied out, before the
    swap), it restarts on the same port, resumes from the latest
    snapshot, the re-armed trigger finishes the interrupted migration,
    and both workers complete every iteration.  Every on-disk snapshot
    holds EITHER the pre-kill plan or the post-migration plan — never a
    torn mixture."""
    from repro.api import RunSpec
    from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                        raise_on_failure)

    ckpt = tmp_path / "ckpt"
    spec = RunSpec.from_dict({
        "model": {"arch": ARCH, "smoke": True},
        "ps": {"kind": "sharded", "shards": 2, "workers": 2,
               "apply": "fused"},
        "wire": {"format": "packed", "delta_pull": True},
        "sync": {"mode": "dssp"},
        "transport": {"kind": "tcp"},
        # a snapshot must land between the first pushes and the kill
        "ft": {"snapshot_every_s": 0.01, "dir": str(ckpt), "resume": True,
               "reconnect_tries": 10, "reconnect_base_s": 0.1,
               "reconnect_max_s": 2.0, "reshard_shards": 3,
               "reshard_round": 8, "fault_kill_mid_reshard": True,
               "fault_seed": 7},
    })
    sp = ServerProcess(spec)
    addr = sp.start()
    pool = ProcessWorkerPool(addr, WorkerTask.from_spec(spec, 12), 2)
    pool.start()
    try:
        assert sp.wait_dead(180.0), "mid-migration kill never fired"
        addr2 = sp.restart()
        assert addr2 == addr
        assert sp.resumed_step is not None and sp.resumed_step > 0
        results = pool.join(timeout=300.0)
        raise_on_failure(results)
        assert [r.iterations_done for r in results] == [12, 12]
    finally:
        pool.terminate()
        sp.stop()
        sp.kill()

    # -- post-mortem: no snapshot is ever torn ------------------------
    mgr = CheckpointManager(str(ckpt), keep=spec.ft.keep)
    steps = mgr.steps()
    assert steps
    arities = set()
    for s in steps:
        with open(os.path.join(mgr._step_dir(s), "manifest.json")) as f:
            ex = json.load(f)["extras"]
        # internally consistent: the shard list, version vector and
        # arity agree (epoch-stable capture retries across a racing
        # migration rather than mixing two plans)
        assert len(ex["shards"]) == ex["n_shards"] == len(ex["versions"])
        assert ex["n_shards"] in (2, 3)
        arities.add((ex["n_shards"], ex["reshard_epoch"]))
    # epoch and arity move together: 2 shards only at epoch 0, 3 only
    # after the migration bumped it
    for n, e in arities:
        assert (n == 2 and e == 0) or (n == 3 and e >= 1)
    # the re-armed trigger finished the interrupted migration in the
    # second incarnation: the final snapshot is post-migration
    with open(os.path.join(mgr._step_dir(steps[-1]),
                           "manifest.json")) as f:
        final = json.load(f)["extras"]
    assert final["n_shards"] == 3
    losses = [p[2] for p in final["metrics"]["loss_trajectory"]]
    assert len(losses) >= 12 and all(math.isfinite(x) for x in losses)


# ============================================================ session wiring
def test_session_ft_rig_snapshots_and_resumes(tmp_path):
    """The declarative path: a RunSpec with an ``ft`` block makes the
    session snapshot while training and a second session resume."""
    from repro.api import build_session

    base = {
        "model": {"arch": ARCH, "smoke": True},
        "ps": {"kind": "sharded", "shards": 2, "workers": 2,
               "apply": "fused"},
        "wire": {"format": "packed"},
        "sync": {"mode": "asp"},
        "transport": {"kind": "inproc"},
        "ft": {"snapshot_every_s": 0.05, "dir": str(tmp_path),
               "resume": False},
    }
    with build_session(base) as session:
        out = session.run(4)
    assert out["ft"]["snapshots"] >= 1
    assert out["ft"]["resumed_step"] is None
    assert out["ft"]["latest_step"] is not None

    resume = dict(base, ft={"snapshot_every_s": 0.0,
                            "dir": str(tmp_path), "resume": True})
    with build_session(resume) as session:
        out2 = session.run(2)
    # close() takes one final snapshot after metrics() was read, so the
    # resumed step is at least the last step the first session reported
    assert out2["ft"]["resumed_step"] >= out["ft"]["latest_step"]
