"""Serving tier (``repro.serve``): spec rules, the MSG_SUB no-seat
invariant, delta-refresh bitwise consistency, the freshness admission
gate, the batching queue, and the train-while-serving e2e.

The e2e spawns REAL OS processes (2 tcp training workers + 2 serving
replicas against one live server) and checks the run's acceptance
contract: loss recorded, served versions advancing, zero
staleness-bound violations, serve spans in the merged trace.
"""

from __future__ import annotations

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy_factory
from repro.obs.trace import TRACE
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.serve import (
    BatchQueue,
    DecodeRequest,
    DirectSubscription,
    ParamSubscriber,
    Refresher,
    aggregate_serve,
)
from repro.transport import PSServerEndpoint
from repro import wireformat as wf

pytestmark = pytest.mark.filterwarnings("ignore::UserWarning")


# ---------------------------------------------------------------- helpers
def tiny_params():
    return {"w": jnp.ones((48, 32), jnp.float32),
            "b": jnp.zeros((17,), jnp.float32)}


def make_server(n_workers=1, n_shards=2, policy="asp", **pkw):
    return ShardedParameterServer(
        tiny_params(),
        make_policy_factory(policy, n_workers=n_workers, staleness=2,
                            s_lower=0, s_upper=2, **pkw),
        lambda: ServerOptimizer(lr=0.05),
        n_workers, n_shards)


def make_subscriber(server, replica_id=9):
    layout = server.plan.wire_layout()
    sub = DirectSubscription(server, replica_id)
    return ParamSubscriber(sub, layout, replica_id=replica_id), layout


def push_random(server, rng, layout, worker=0):
    g = rng.randn(layout.total_rows, wf.WIRE_LANES).astype(np.float32)
    server.push_packed(worker, jnp.asarray(g))


def wait_version(server, target, timeout=10.0):
    deadline = time.monotonic() + timeout
    while server.version < target:
        if time.monotonic() > deadline:
            raise TimeoutError(f"server stuck at {server.version} < "
                               f"{target}")
        time.sleep(0.002)


# ============================================================ spec rules
class TestServeSpec:
    def base(self, **serve_kw):
        from repro.api import (ModelSpec, RunSpec, ServeSpec, ServerSpec,
                               WireSpec)
        return dict(
            model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
            ps=ServerSpec(kind="sharded", shards=2, workers=2,
                          apply="fused"),
            wire=WireSpec(format="packed", delta_pull=True),
            serve=ServeSpec(replicas=1, **serve_kw))

    def test_valid_serve_spec_builds(self):
        from repro.api import RunSpec
        spec = RunSpec(**self.base())
        assert spec.serve.replicas == 1

    def test_serve_needs_a_parameter_server(self):
        from repro.api import RunSpec, ServerSpec, SpecError
        kw = self.base()
        kw["ps"] = ServerSpec(kind="none")
        with pytest.raises(SpecError, match="serve.replicas"):
            RunSpec(**kw)

    def test_serve_needs_delta_pull(self):
        from repro.api import RunSpec, SpecError, WireSpec
        kw = self.base()
        kw["wire"] = WireSpec(format="packed", delta_pull=False)
        with pytest.raises(SpecError, match="delta"):
            RunSpec(**kw)

    def test_serve_rejects_custom_arch(self):
        from repro.api import ModelSpec, RunSpec, SpecError
        kw = self.base()
        kw["model"] = ModelSpec(arch="custom")
        with pytest.raises(SpecError, match="custom"):
            RunSpec(**kw)

    @pytest.mark.parametrize("field,value", [
        ("replicas", -1), ("refresh_every_s", 0.0),
        ("staleness_bound", -1), ("batch_window_ms", -0.5),
        ("max_batch", 0), ("requests", 0), ("request_every_ms", -1.0),
        ("start_at_version", -1), ("prompt_len", 0), ("max_new", 0),
    ])
    def test_field_validation(self, field, value):
        from repro.api import ServeSpec, SpecError
        with pytest.raises(SpecError):
            ServeSpec(**{field: value})

    def test_serve_round_trips_through_dict(self):
        from repro.api import RunSpec, ServeSpec
        kw = self.base(staleness_bound=7, requests=11,
                       request_every_ms=3.5, start_at_version=2)
        spec = RunSpec(**kw)
        back = RunSpec.from_dict(spec.to_dict())
        assert back.serve == spec.serve
        assert back.serve.staleness_bound == 7


# ============================================================ MSG_SUB
class TestSubscription:
    def test_sub_frame_codec_roundtrip(self):
        f = wf.Frame(kind=wf.MSG_SUB, worker=5)
        g = wf.decode_frame(wf.encode_frame(f))
        assert (g.kind, g.worker) == (wf.MSG_SUB, 5)

    def test_subscriber_takes_no_barrier_seat(self):
        """2 BSP workers must release with a subscriber present: had
        the SUB taken a seat, the round barrier would wait for a third
        push that never comes."""
        server = make_server(n_workers=2, policy="bsp")
        endpoint = PSServerEndpoint(server)
        for w in (0, 1):
            r = endpoint.handle(wf.Frame(kind=wf.MSG_HELLO, worker=w))
            assert r.kind == wf.MSG_OK
        r = endpoint.handle(wf.Frame(kind=wf.MSG_SUB, worker=9))
        assert r.kind == wf.MSG_OK
        assert r.clock == server.version
        wire = np.zeros((endpoint.wire_rows(), wf.WIRE_LANES),
                        np.float32)
        replies = []

        def push(w):
            replies.append(endpoint.handle(
                wf.Frame(kind=wf.MSG_PUSH, worker=w, payload=wire)).kind)

        threads = [threading.Thread(target=push, args=(w,))
                   for w in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not any(t.is_alive() for t in threads), \
            "BSP round blocked — the subscriber took a barrier seat"
        assert replies == [wf.MSG_OK, wf.MSG_OK]
        server.stop()

    def test_dead_subscriber_is_not_removed_as_worker(self):
        server = make_server(n_workers=2)
        endpoint = PSServerEndpoint(server)
        endpoint.handle(wf.Frame(kind=wf.MSG_HELLO, worker=0))
        endpoint.handle(wf.Frame(kind=wf.MSG_SUB, worker=9))
        removed = []
        orig = server.remove_worker
        server.remove_worker = lambda w: (removed.append(w), orig(w))
        endpoint.on_disconnect(9)   # subscriber: unregister only
        assert removed == []
        endpoint.on_disconnect(0)   # worker: seat must be freed
        assert removed == [0]
        server.stop()

    def test_sub_rejected_on_per_shard_endpoint(self):
        server = make_server(n_shards=2)
        endpoint = PSServerEndpoint(server, shards=[0])
        r = endpoint.handle(wf.Frame(kind=wf.MSG_SUB, worker=9))
        assert r.kind == wf.MSG_ERR
        assert "full-store" in r.error
        server.stop()


# ============================================================ refresh
class TestRefresh:
    def test_unbootstrapped_is_never_fresh(self):
        server = make_server()
        ps, _ = make_subscriber(server)
        assert ps.staleness() == ParamSubscriber.UNBOOTSTRAPPED
        assert ps.refresh()
        assert ps.staleness() == 0
        server.stop()

    def test_delta_refresh_matches_full_pull_bitwise(self):
        """The resident buffer after N delta refreshes must equal a
        full pull byte-for-byte — region patching reconstructs the
        exact store, not an approximation of it."""
        server = make_server(n_workers=1, n_shards=3)
        server.add_worker(0)
        ps, layout = make_subscriber(server)
        assert ps.refresh()
        rng = np.random.RandomState(0)
        for i in range(4):
            push_random(server, rng, layout)
            wait_version(server, (i + 1) * 3)
            assert ps.refresh()
            buf, ver = ps.snapshot()
            full = np.asarray(server.pull_packed(0))
            assert buf.tobytes() == full.tobytes()
            assert ver == server.version
        assert ps.full_refreshes == 0  # deltas all the way, never a
        server.stop()                  # dominance-mismatch fallback

    def test_stopped_server_serves_final_weights(self):
        """A replica that trails at stop time must catch up to the
        FINAL weights before freezing — stopping at an older vector
        would pin pre-final parameters forever."""
        server = make_server(n_workers=1)
        server.add_worker(0)
        ps, layout = make_subscriber(server)
        rng = np.random.RandomState(1)
        push_random(server, rng, layout)
        wait_version(server, 2)
        server.stop()
        assert ps.refresh()         # the catch-up delta still lands
        assert not ps.refresh()     # now caught up: STOP freezes it
        assert ps.stopped
        buf, ver = ps.snapshot()
        assert buf.tobytes() == np.asarray(
            server.pull_packed(0)).tobytes()
        assert ver == server.version
        assert ps.wait_fresh(0) == 0  # frozen weights are fresh forever

    def test_wait_fresh_blocks_until_refresh_lands(self):
        server = make_server(n_workers=1)
        server.add_worker(0)
        ps, layout = make_subscriber(server)
        ps.refresh()
        rng = np.random.RandomState(2)
        push_random(server, rng, layout)
        wait_version(server, 2)
        assert ps.staleness() == 2
        TRACE.enable(source="test")
        try:
            admitted = []
            t = threading.Thread(
                target=lambda: admitted.append(ps.wait_fresh(0)))
            t.start()
            time.sleep(0.3)
            assert t.is_alive(), "gate admitted a stale replica"
            assert ps.refresh_needed.is_set()
            ps.refresh()
            t.join(timeout=10.0)
            assert admitted == [0]
            assert ps.blocks == 1
            names = {e["name"] for e in TRACE.drain()}
            assert "staleness_block" in names
            assert "replica_refresh" in names
        finally:
            TRACE.disable()
        server.stop()

    @pytest.mark.parametrize("seed,bound", [(0, 0), (1, 1), (2, 3)])
    def test_admission_staleness_bounded_under_live_updates(self, seed,
                                                           bound):
        """The freshness property: against a seeded schedule of live
        pushes, EVERY admission the gate grants is within the bound —
        measured against the server's version at admission time."""
        server = make_server(n_workers=1)
        server.add_worker(0)
        ps, layout = make_subscriber(server)
        refresher = Refresher(ps, refresh_every_s=0.002)
        refresher.start()
        rng = np.random.RandomState(seed)
        stop = threading.Event()

        def trainer():
            while not stop.is_set():
                push_random(server, rng, layout)
                time.sleep(rng.uniform(0.0, 0.004))

        t = threading.Thread(target=trainer, daemon=True)
        t.start()
        try:
            pace = np.random.RandomState(seed + 100)
            admitted = [ps.wait_fresh(bound) for _ in range(25)
                        if not time.sleep(pace.uniform(0.0, 0.003))]
            assert len(admitted) == 25
            assert all(a <= bound for a in admitted), admitted
        finally:
            stop.set()
            t.join(timeout=10.0)
            refresher.stop()
            server.stop()


# ============================================================ batching
class TestBatchQueue:
    def req(self, i):
        return DecodeRequest(request_id=i,
                             prompt=np.zeros(4, np.int32),
                             enqueue_t=time.perf_counter())

    def test_fifo_batch_up_to_max(self):
        q = BatchQueue()
        for i in range(5):
            q.submit(self.req(i))
        batch = q.next_batch(max_batch=3, window_s=0.0)
        assert [r.request_id for r in batch] == [0, 1, 2]
        batch = q.next_batch(max_batch=3, window_s=0.0)
        assert [r.request_id for r in batch] == [3, 4]

    def test_linger_window_collects_late_arrivals(self):
        q = BatchQueue()
        q.submit(self.req(0))
        threading.Timer(0.05, lambda: q.submit(self.req(1))).start()
        batch = q.next_batch(max_batch=4, window_s=0.5)
        assert len(batch) == 2

    def test_close_drains_then_returns_none(self):
        q = BatchQueue()
        q.submit(self.req(0))
        q.close()
        assert len(q.next_batch(2, 0.0)) == 1
        assert q.next_batch(2, 0.0) is None
        with pytest.raises(RuntimeError):
            q.submit(self.req(1))

    def test_next_batch_blocks_until_submit(self):
        q = BatchQueue()
        got = []
        t = threading.Thread(
            target=lambda: got.append(q.next_batch(2, 0.0)))
        t.start()
        time.sleep(0.1)
        assert t.is_alive()
        q.submit(self.req(7))
        t.join(timeout=10.0)
        assert [r.request_id for r in got[0]] == [7]

    def test_aggregate_handles_empty_and_none(self):
        agg = aggregate_serve([None])
        assert agg["requests"] == 0 and agg["violations"] == 0


# ============================================================ e2e
def _serve_spec(trace_path=""):
    from repro.api import (DataSpec, ModelSpec, ObsSpec, RunSpec,
                           ServeSpec, ServerSpec, SyncSpec,
                           TransportSpec, WireSpec)
    return RunSpec(
        model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
        data=DataSpec(seq_len=32, global_batch=4),
        ps=ServerSpec(kind="sharded", shards=2, workers=2,
                      apply="fused"),
        sync=SyncSpec(mode="dssp", s_lower=1, s_upper=4),
        wire=WireSpec(format="packed", delta_pull=True),
        transport=TransportSpec(kind="tcp", endpoint=True),
        obs=ObsSpec(trace=bool(trace_path), trace_path=trace_path),
        serve=ServeSpec(replicas=2, requests=6, request_every_ms=100.0,
                        start_at_version=1, prompt_len=8, max_new=4,
                        max_batch=4, staleness_bound=4))


def test_e2e_threaded_train_and_serve():
    """ps-threads engine: replica threads against the in-heap server."""
    import dataclasses

    from repro.api import TransportSpec, build_session
    spec = dataclasses.replace(_serve_spec(), transport=TransportSpec())
    with build_session(spec) as session:
        m = session.run(steps=24)
    serve = m["serve"]
    assert serve["requests"] == 12
    assert serve["violations"] == 0
    assert serve["version_max"] > 0
    assert m["final_loss"] is not None


def test_e2e_tcp_train_and_serve_traced(tmp_path):
    """The acceptance e2e: one RunSpec, 2 tcp worker processes
    training while 2 replica processes serve via delta pulls — loss
    recorded, served versions advance, zero staleness violations, and
    the serve spans land in the merged trace."""
    from repro.api import build_session
    trace = str(tmp_path / "serve_trace.jsonl")
    with build_session(_serve_spec(trace)) as session:
        m = session.run(steps=24)
    assert m["final_loss"] is not None
    assert m["applied_updates"] > 0
    serve = m["serve"]
    assert serve["requests"] == 12, serve
    assert serve["violations"] == 0, serve
    assert serve["staleness_max"] <= 4
    assert serve["version_max"] > 0, \
        "replicas never served an updated version"
    names = set()
    with open(trace) as fh:
        for line in fh:
            names.add(json.loads(line)["name"])
    for want in ("replica_refresh", "decode_batch", "push",
                 "compute_step"):
        assert want in names, f"{want} missing from {sorted(names)}"
