"""Transport layer: frame codec, process-boundary round trips, failure
paths, and the end-to-end process-isolated training run.

The process tests spawn REAL OS worker processes (multiprocessing
``spawn`` — never fork, jax is live in the parent) and verify the
packed (rows, 512) buffer survives the wire bitwise, for both ``tcp``
and ``shmem``, with and without frame-level int8 compression.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import os
import socket
import struct
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy_factory
from repro.perfcount import TRANSPORT
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.transport import (
    PSServerEndpoint,
    ShardRouter,
    TransportClosed,
    connect,
    make_transport,
)
from repro import wireformat as wf

pytestmark = pytest.mark.filterwarnings(
    "ignore::UserWarning")  # mp resource_tracker chatter on some paths


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


def serve(kind, server, n_workers=1, shards=None):
    endpoint = PSServerEndpoint(server, shards=shards)
    transport = make_transport(kind, n_workers=n_workers)
    transport.serve(endpoint)
    return endpoint, transport


def digest(arr) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()


# ============================================================ frame codec
class TestFrameCodec:
    def test_f32_roundtrip_bitwise(self):
        a = np.random.RandomState(0).randn(16, wf.WIRE_LANES)
        a = a.astype(np.float32)
        f = wf.Frame(kind=wf.MSG_PUSH, worker=7, shard=3, clock=99,
                     payload=a)
        g = wf.decode_frame(wf.encode_frame(f))
        assert g.payload.tobytes() == a.tobytes()
        assert (g.kind, g.worker, g.shard, g.clock) == (
            wf.MSG_PUSH, 7, 3, 99)

    def test_bf16_roundtrip_bitwise(self):
        import ml_dtypes
        a = np.random.RandomState(1).randn(8, wf.WIRE_LANES)
        a = a.astype(ml_dtypes.bfloat16)
        g = wf.decode_frame(wf.encode_frame(
            wf.Frame(kind=wf.MSG_PULL, payload=a)))
        assert g.payload.dtype == a.dtype
        assert g.payload.tobytes() == a.tobytes()

    def test_int8_compression_shrinks_and_bounds_error(self):
        a = np.random.RandomState(2).randn(8, wf.WIRE_LANES)
        a = a.astype(np.float32)
        raw = wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH, payload=a))
        packed = wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH, payload=a),
                                 compress="int8")
        assert len(packed) - wf.HEADER_SIZE == \
            (len(raw) - wf.HEADER_SIZE) // 4
        g = wf.decode_frame(packed)
        assert g.flags & wf.FLAG_INT8
        scale = np.max(np.abs(a)) / 127.0
        assert np.max(np.abs(g.payload - a)) <= scale * 0.5 + 1e-7

    def test_int8_decode_is_deterministic(self):
        a = np.random.RandomState(3).randn(8, wf.WIRE_LANES)
        a = a.astype(np.float32)
        raw = wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH, payload=a),
                              compress="int8")
        assert wf.decode_frame(raw).payload.tobytes() == \
            wf.decode_frame(raw).payload.tobytes()

    def test_error_frame(self):
        f = wf.decode_frame(wf.encode_frame(
            wf.Frame(kind=wf.MSG_ERR, error="kaboom")))
        assert f.error == "kaboom" and f.payload is None

    @pytest.mark.parametrize("mangle", [
        lambda b: b[:20],                                 # short header
        lambda b: b"XXXX" + b[4:],                        # bad magic
        lambda b: b[:4] + bytes([99]) + b[5:],            # bad version
        lambda b: b[:5] + bytes([200]) + b[6:],           # unknown kind
        lambda b: b[:6] + bytes([77]) + b[7:],            # unknown dtype
        lambda b: b[:-8],                                 # truncated body
    ])
    def test_header_validation_rejects(self, mangle):
        a = np.zeros((8, wf.WIRE_LANES), np.float32)
        raw = wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH, payload=a))
        before = TRANSPORT.header_rejects
        with pytest.raises(wf.FrameError):
            wf.decode_frame(mangle(raw))
        assert TRANSPORT.header_rejects == before + 1

    def test_length_field_must_match_rows(self):
        a = np.zeros((8, wf.WIRE_LANES), np.float32)
        raw = bytearray(wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH,
                                                 payload=a)))
        # corrupt payload_len (offset: 4s B B B B i i q I -> 28..36)
        struct.pack_into("<Q", raw, 28, 12345)
        with pytest.raises(wf.FrameError):
            wf.decode_frame(bytes(raw))

    def test_non_wire_shape_rejected_on_encode(self):
        with pytest.raises(wf.FrameError):
            wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH,
                                     payload=np.zeros((4, 100))))


# ============================================= process-boundary round trip
def _echo_child(address, seed, q):
    """Spawned child: echoes a deterministic buffer through the server
    endpoint (plain + int8 frames) and reports digests of what came
    back, plus a digest of the pulled params."""
    try:
        client = connect(address, 0)
        rows = client.hello()
        rng = np.random.RandomState(seed)
        buf = rng.randn(rows, 512).astype(np.float32)
        back = client.echo(buf)
        back8 = client.echo(buf, compress="int8")
        pulled = client.pull_packed()
        client.bye()
        client.close()
        q.put({"echo": digest(back), "echo8": digest(back8),
               "pull": digest(pulled), "rows": rows})
    except BaseException as e:
        q.put({"error": repr(e)})


@pytest.mark.parametrize("kind", ["tcp", "shmem"])
def test_bitwise_roundtrip_across_process_boundary(kind):
    server = make_server()
    endpoint, transport = serve(kind, server)
    rows = server.plan.wire_layout().total_rows
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_echo_child, args=(transport.address(), 42, q),
                    daemon=True)
    p.start()
    got = q.get(timeout=120.0)
    p.join(timeout=30.0)
    server.stop()
    transport.shutdown()
    assert "error" not in got, got
    assert got["rows"] == rows
    # Same machine, same seed: the child's buffer is reproducible here,
    # so a bitwise-equal digest proves the frame survived two crossings
    # of a real process boundary unchanged.
    rng = np.random.RandomState(42)
    buf = rng.randn(rows, 512).astype(np.float32)
    assert got["echo"] == digest(buf)
    # int8 is lossy but deterministic: quantize+dequantize locally and
    # require the over-the-wire version to match BITWISE.
    deq = wf.decode_frame(wf.encode_frame(
        wf.Frame(kind=wf.MSG_ECHO, payload=buf), compress="int8")).payload
    assert got["echo8"] == digest(deq)
    # And the pull: the server's packed params, bitwise.
    assert got["pull"] == digest(np.asarray(server.pull_packed()))


def _push_child(address, seed, q):
    try:
        client = connect(address, 0)
        rows = client.hello()
        rng = np.random.RandomState(seed)
        grads = rng.randn(rows, 512).astype(np.float32)
        ok = client.push_packed(grads)
        after = client.pull_packed()
        client.bye()
        client.close()
        q.put({"ok": ok, "after": digest(after)})
    except BaseException as e:
        q.put({"error": repr(e)})


@pytest.mark.parametrize("kind", ["tcp", "shmem"])
def test_push_across_boundary_matches_local_push(kind):
    """A spawned process's push must land bit-identically to the same
    push made locally (the full pull-push-apply-pull cycle)."""
    remote = make_server()
    local = make_server()
    endpoint, transport = serve(kind, remote)
    rows = remote.plan.wire_layout().total_rows
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_push_child, args=(transport.address(), 7, q),
                    daemon=True)
    p.start()
    got = q.get(timeout=120.0)
    p.join(timeout=30.0)
    remote.stop()
    transport.shutdown()
    assert "error" not in got, got
    assert got["ok"]
    grads = np.random.RandomState(7).randn(rows, 512).astype(np.float32)
    local.push_packed(0, jnp.asarray(grads))
    assert got["after"] == digest(np.asarray(local.pull_packed()))


# ==================================================== shard-routed endpoints
def test_per_shard_routing_across_two_endpoints():
    """Different shards behind different endpoints (even different
    backends) apply exactly like one full-buffer push."""
    routed = make_server()
    whole = make_server()
    layout = routed.plan.wire_layout()
    ep0, t0 = serve("tcp", routed, shards=[0])
    ep1, t1 = serve("shmem", routed, n_workers=1, shards=[1])
    c0, c1 = t0.connect(0), t1.connect(0)
    c0.hello(), c1.hello()
    router = ShardRouter({0: c0, 1: c1}, layout.shard_rows)

    wire = np.random.RandomState(5).randn(
        layout.total_rows, 512).astype(np.float32)
    assert router.push_packed(wire)
    whole.push_packed(0, jnp.asarray(wire))
    assert digest(router.pull_packed()) == \
        digest(np.asarray(whole.pull_packed()))
    assert routed.shard_versions() == whole.shard_versions()

    # frames for a shard an endpoint does not serve are rejected
    with pytest.raises(wf.FrameError):
        c0.pull_packed(shard=1)
    with pytest.raises(wf.FrameError):
        c0.pull_packed()  # routed endpoints require an explicit shard
    routed.stop(), whole.stop()
    t0.shutdown(), t1.shutdown()


def test_routed_push_rejects_global_gating():
    server = ShardedParameterServer(
        tiny_params(), make_policy_factory("asp", n_workers=1),
        lambda: ServerOptimizer(lr=0.05), 1, 2, gating="global")
    with pytest.raises(ValueError, match="gating"):
        server.push_packed_shard(
            0, 0, jnp.zeros((server.plan.wire_layout().shard_rows[0], 512)))
    server.stop()


# ========================================================== failure paths
def _truncating_child(address, q):
    """Connects, HELLOs, then sends HALF a push frame and dies — the
    'worker process killed mid-push' wire state."""
    try:
        client = connect(address, 0)
        rows = client.hello()
        buf = np.ones((rows, 512), np.float32)
        raw = wf.encode_frame(wf.Frame(kind=wf.MSG_PUSH, worker=0,
                                       payload=buf))
        sock = client.channel._sock
        sock.sendall(raw[:len(raw) // 2])
        q.put("sent-half")
    except BaseException as e:
        q.put(f"error {e!r}")
    # flush the queue's feeder thread, THEN die without any clean-up
    q.close()
    q.join_thread()
    os._exit(1)


def test_worker_killed_mid_push_frees_its_barrier_seat():
    """BSP gates worker 1 on worker 0's pushes; killing worker 0 halfway
    through a push frame must (a) not crash the server and (b) remove
    worker 0 from the barrier group so worker 1 is released."""
    server = make_server(n_workers=2, policy="bsp")
    endpoint, transport = serve("tcp", server)
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_truncating_child,
                    args=(transport.address(), q), daemon=True)
    p.start()
    assert q.get(timeout=120.0) == "sent-half"
    p.join(timeout=30.0)

    # The server notices the dead connection and frees the seat.
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if all(0 not in st.tracker.counts for st in server.shards):
            break
        time.sleep(0.05)
    assert all(0 not in st.tracker.counts for st in server.shards), \
        "dead worker still holds a barrier seat"

    # Worker 1's BSP push does not block on the corpse.
    c1 = transport.connect(1)
    c1.hello()
    rows = server.plan.wire_layout().total_rows
    t0 = time.monotonic()
    assert c1.push_packed(np.zeros((rows, 512), np.float32))
    assert time.monotonic() - t0 < 10.0
    c1.bye()
    server.stop()
    transport.shutdown()


def test_tcp_garbage_header_gets_error_reply():
    server = make_server()
    endpoint, transport = serve("tcp", server)
    _, host, port = transport.address()
    with socket.create_connection((host, port), timeout=10.0) as sock:
        sock.sendall(b"GARBAGE!" * 8)  # 64 junk bytes >= one header
        reply = sock.recv(1 << 16)
    frame = wf.decode_frame(reply)
    assert frame.kind == wf.MSG_ERR and "magic" in frame.error
    # the server keeps serving fresh connections
    c = transport.connect(0)
    c.hello()
    out = c.echo(np.ones((8, 512), np.float32))
    assert out.shape == (8, 512)
    server.stop()
    transport.shutdown()


def test_tcp_oversized_length_field_rejected():
    server = make_server()
    endpoint, transport = serve("tcp", server)
    _, host, port = transport.address()
    raw = bytearray(wf.encode_frame(wf.Frame(
        kind=wf.MSG_PUSH, worker=0,
        payload=np.zeros((8, 512), np.float32))))
    struct.pack_into("<Q", raw, 28, wf.MAX_PAYLOAD + 1)
    with socket.create_connection((host, port), timeout=10.0) as sock:
        sock.sendall(bytes(raw))
        frame = wf.decode_frame(sock.recv(1 << 16))
    assert frame.kind == wf.MSG_ERR and "exceeds" in frame.error
    server.stop()
    transport.shutdown()


@pytest.mark.parametrize("kind", ["tcp", "shmem"])
def test_clean_shutdown_unblocks_waiting_dssp_workers(kind):
    """A DSSP worker blocked in the policy gate (too far ahead of a
    silent peer) must be released by server.stop() with a STOP reply —
    the clean-shutdown contract."""
    server = make_server(n_workers=2, policy="dssp")
    endpoint, transport = serve(kind, server, n_workers=2)
    rows = server.plan.wire_layout().total_rows
    released = threading.Event()
    state = {}

    def runner():
        c = transport.connect(0)
        c.hello()
        alive = True
        for i in range(50):  # hits the DSSP upper threshold long before 50
            alive = c.push_packed(
                np.zeros((rows, 512), np.float32), clock=i)
            if not alive:
                break
        state["alive"] = alive
        released.set()
        c.close()

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    # Let the worker run into the gate (worker 1 never pushes).
    time.sleep(1.0)
    assert not released.is_set(), "worker was never gated — bad setup"
    server.stop()
    assert released.wait(timeout=15.0), \
        "stop() did not unblock the gated DSSP worker"
    assert state["alive"] is False  # the release was a STOP, not an OK
    t.join(timeout=10.0)
    transport.shutdown()


def test_client_surfaces_shutdown_as_transport_closed():
    server = make_server()
    endpoint, transport = serve("tcp", server)
    c = transport.connect(0)
    c.hello()
    server.stop()
    transport.shutdown()
    with pytest.raises((TransportClosed, wf.FrameError)):
        for _ in range(3):  # first call may still see a buffered STOP
            c.pull_packed()


# ================================================= end-to-end process run
def test_e2e_tcp_processes_match_inproc_threads():
    """Acceptance: train.py's --transport tcp path (3 spawned worker
    processes, DSSP) reaches the same final-loss tolerance as the
    threaded inproc packed path."""
    from repro.configs import get_smoke_config
    from repro.data.synthetic import DataConfig
    from repro.launch.train import train_ps

    cfg = get_smoke_config("xlstm-125m")
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4)
    kw = dict(sync="dssp", n_steps=24, lr=0.02, n_shards=2, n_workers=3,
              s_lower=0, s_upper=3, straggler=1.5, arch="xlstm-125m",
              smoke=True)
    inproc = train_ps(cfg, data_cfg, transport="inproc", **kw)
    tcp = train_ps(cfg, data_cfg, transport="tcp", **kw)

    assert tcp.version > 0 and tcp.metrics.total_pushes >= 3
    losses_in = [l for _, _, l in inproc.metrics.loss_trajectory]
    losses_tcp = [l for _, _, l in tcp.metrics.loss_trajectory]
    assert losses_in and losses_tcp
    fin_in, fin_tcp = losses_in[-1], losses_tcp[-1]
    assert np.isfinite(fin_in) and np.isfinite(fin_tcp)
    # Same model/data/steps either side of the process boundary: the
    # final losses must agree to the asynchrony tolerance.
    assert abs(fin_tcp - fin_in) <= max(0.15 * abs(fin_in), 0.15), \
        (fin_in, fin_tcp)
