"""Coalesced server apply + version-delta pulls (the PR-5 tentpole).

Covers the acceptance surface:

* ``fused_update_batched`` is bitwise-identical to K sequential
  ``fused_update`` launches for f32 state at every K (and for every
  dtype at K=1), and matches the jnp oracle (the bitwise comparisons
  are computed once in a child process with XLA:CPU pinned to SSE4.2 —
  see ``pinned``);
* a coalescing window of 1, and a window fed strictly sequential
  pushes, match the uncoalesced packed path bitwise;
* W concurrent pushes into a window of W fold through ONE batched
  launch per shard (launches per round == shards, not shards x
  workers) with per-worker gating intact;
* ``pull_delta`` with a current vector is an empty delta (and the
  assembled buffer stays bitwise-equal to ``pull_packed``); partial
  advances ship only the advanced shards' bytes; vector mismatches
  fall back to a full snapshot;
* the ``PULL_DELTA``/``DELTA`` frame pair round-trips through the
  codec and across a real tcp process boundary;
* the ``pull_packed`` snapshot cache survives a concurrent push+pull
  hammer with its key always describing its contents (the PR-5
  race-window regression test);
* a 4-worker DSSP tcp run through ``repro.api`` with coalescing +
  delta pulls reaches the same final-loss tolerance as the plain
  packed threads run.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import wireformat as wf
from repro.api.protocol import DeltaPull
from repro.core.policies import make_policy_factory
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.perfcount import WIRE

warnings.filterwarnings("ignore", category=DeprecationWarning)


def make_params():
    rng = np.random.RandomState(0)
    return {
        "w0": jnp.asarray(rng.randn(24, 512).astype(np.float32)),
        "w1": jnp.asarray(rng.randn(16, 128).astype(np.float32)),
        "b": jnp.asarray(rng.randn(300).astype(np.float32)),
        "s": jnp.float32(rng.randn()),
    }


def grads_like(params, seed):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32))
        if p.shape else jnp.float32(rng.randn()), params)


def make_sharded(params, *, n_workers=4, n_shards=2, policy="asp",
                 coalesce=1, coalesce_wait=None, momentum=0.9,
                 lr=0.05, damping=False):
    from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
    return ShardedParameterServer(
        params, make_policy_factory(policy, n_workers=n_workers),
        lambda: ServerOptimizer(lr=lr, momentum=momentum,
                                staleness_damping=damping),
        n_workers=n_workers, n_shards=n_shards,
        coalesce=coalesce, coalesce_wait=coalesce_wait)


# ===================================================== pinned CPU ISA
KS = [1, 2, 3, 5]
SHAPES = [(16, 512), (40, 512), (7, 13)]


def _batched_vs_sequential(k, shape):
    """(batched p, m) and (sequential p, m) of one kernel case, plus the
    inputs."""
    rng = np.random.RandomState(k)
    p = jnp.asarray(rng.randn(*shape).astype(np.float32))
    m = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gs = jnp.asarray(rng.randn(k, *shape).astype(np.float32))
    scales = [1.0 / (1 + j) for j in range(k)]
    po, mo = kops.fused_update_batched(p, m, gs, lr=0.01, beta=0.9,
                                       scales=scales)
    ps_, ms_ = p, m
    for j in range(k):
        ps_, ms_ = kops.fused_update(ps_, ms_, gs[j], lr=0.01, beta=0.9,
                                     scale=scales[j])
    return (po, mo), (ps_, ms_), (p, m, gs, scales)


W, S = 4, 2


def _concurrent_round():
    """W concurrent pushes of one gradient into a window of W, beside W
    sequential pushes into an uncoalesced server.  Returns both servers
    and the wire counters of the concurrent round."""
    params = make_params()
    co = make_sharded(params, n_workers=W, n_shards=S, coalesce=W,
                      coalesce_wait=5.0)
    base = make_sharded(params, n_workers=W, n_shards=S, coalesce=1)
    # identical grads for every worker: the sequential in-kernel fold is
    # then order-independent, so the concurrent batch must be BITWISE
    # equal to W sequential pushes regardless of enqueue order.
    wire = base.plan.pack(grads_like(params, 7))
    for w in range(W):
        base.push_packed(w, wire)
    co.push_packed(0, wire)        # warm the compile caches
    base2 = make_sharded(params, n_workers=W, n_shards=S, coalesce=1)
    for w in range(W):
        base2.push_packed(w, wire)

    co2 = make_sharded(params, n_workers=W, n_shards=S, coalesce=W,
                       coalesce_wait=5.0)
    WIRE.reset()
    threads = [threading.Thread(target=co2.push_packed, args=(w, wire))
               for w in range(W)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ev = WIRE.snapshot()
    for srv in (co, base):
        srv.stop()
    return co2, base2, ev


def _write_pinned_arrays(path: str) -> None:
    """The child's work: every array the bitwise cases compare."""
    out = {}
    for k in KS:
        for i, shape in enumerate(SHAPES):
            (po, mo), (ps_, ms_), _ = _batched_vs_sequential(k, shape)
            for name, a in (("po", po), ("mo", mo), ("ps", ps_),
                            ("ms", ms_)):
                out[f"{k}-{i}-{name}"] = np.asarray(a)
    co2, base2, _ = _concurrent_round()
    out["window-co"] = np.asarray(co2.pull_packed())
    out["window-base"] = np.asarray(base2.pull_packed())
    for srv in (co2, base2):
        srv.stop()
    np.savez(path, **out)


@pytest.fixture(scope="module")
def pinned(tmp_path_factory):
    """The arrays the bitwise cases compare, computed once in a child
    process with XLA:CPU pinned to SSE4.2.

    On an x86 host with FMA, XLA:CPU contracts multiply-adds into FMAs
    differently in the batched K-step program and in K single-step
    programs, so the two can round apart by an ulp there.  Without FMA
    both programs round every multiply and every add, which is the
    equality the batched kernel promises (and what a TPU computes).
    The flag has to be set before the CPU client starts, hence the
    child."""
    here = pathlib.Path(__file__).resolve().parent
    path = tmp_path_factory.mktemp("pinned_isa") / "arrays.npz"
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_cpu_max_isa=SSE4_2".strip(),
               PYTHONPATH=os.pathsep.join(
                   [str(here.parent / "src"), str(here),
                    os.environ.get("PYTHONPATH", "")]))
    code = ("import sys, test_coalesced_delta as t; "
            "t._write_pinned_arrays(sys.argv[1])")
    proc = subprocess.run([sys.executable, "-c", code, str(path)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    with np.load(path) as arrays:
        return dict(arrays)


# ================================================================ kernel
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("shape", SHAPES)
def test_batched_kernel_bitwise_equals_sequential_launches(k, shape,
                                                          pinned):
    i = SHAPES.index(shape)
    po, mo = pinned[f"{k}-{i}-po"], pinned[f"{k}-{i}-mo"]
    assert np.array_equal(po, pinned[f"{k}-{i}-ps"])
    assert np.array_equal(mo, pinned[f"{k}-{i}-ms"])
    # and the jnp oracle agrees to fp tolerance
    _, _, (p, m, gs, scales) = _batched_vs_sequential(k, shape)
    pr, mr = kref.fused_update_batched_ref(p, m, gs, lr=0.01, beta=0.9,
                                           scales=scales)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_batched_kernel_k1_bitwise_every_dtype(dtype):
    rng = np.random.RandomState(1)
    p = jnp.asarray(rng.randn(16, 512), dtype)
    m = jnp.asarray(rng.randn(16, 512), dtype)
    g = jnp.asarray(rng.randn(1, 16, 512), dtype)
    po, mo = kops.fused_update_batched(p, m, g, lr=0.02, beta=0.9,
                                       scales=[0.5])
    p1, m1 = kops.fused_update(p, m, g[0], lr=0.02, beta=0.9, scale=0.5)
    assert jnp.array_equal(po, p1) and jnp.array_equal(mo, m1)


def test_batched_kernel_rejects_bad_shapes():
    p = jnp.zeros((8, 512))
    m = jnp.zeros((8, 512))
    with pytest.raises(ValueError, match="do not match"):
        kops.fused_update_batched(p, m, jnp.zeros((2, 8, 256)), lr=0.1)
    with pytest.raises(ValueError, match="scales"):
        kops.fused_update_batched(p, m, jnp.zeros((2, 8, 512)), lr=0.1,
                                  scales=[1.0])


# ==================================================== coalesced server
def test_window_of_one_is_bitwise_the_uncoalesced_path():
    params = make_params()
    base = make_sharded(params, coalesce=1)
    co = make_sharded(params, coalesce=4, coalesce_wait=0.0)
    wires = [base.plan.pack(grads_like(params, s)) for s in range(3)]
    for i, w in enumerate(wires):
        base.push_packed(i % 4, w)
        co.push_packed(i % 4, w)   # sequential -> every batch has K=1
    assert co.shard_versions() == base.shard_versions()
    assert jnp.array_equal(co.pull_packed(), base.pull_packed())
    base.stop(), co.stop()


def test_concurrent_window_one_launch_per_shard_per_round(pinned):
    co2, base2, ev = _concurrent_round()
    # ONE batched launch per shard for the whole 4-worker round
    assert ev["pallas_calls"] == S, ev
    assert ev["apply_launches_saved"] == S * (W - 1), ev
    assert co2.shard_versions() == [W] * S          # every push applied
    assert np.array_equal(pinned["window-co"], pinned["window-base"])
    for srv in (co2, base2):
        srv.stop()


def test_coalesced_gating_still_blocks_per_worker():
    """BSP gating across a coalesced window: the barrier still releases
    per worker, so a full round completes and every push applies."""
    params = make_params()
    W = 3
    srv = make_sharded(params, n_workers=W, n_shards=2, policy="bsp",
                       coalesce=W, coalesce_wait=1.0)
    wire = srv.plan.pack(grads_like(params, 3))
    threads = [threading.Thread(target=srv.push_packed, args=(w, wire))
               for w in range(W)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads), "BSP round deadlocked"
    assert srv.shard_versions() == [W, W]
    srv.stop()


# ======================================================== delta pulls
def test_empty_delta_is_bitwise_the_full_snapshot():
    params = make_params()
    srv = make_sharded(params, n_shards=3)
    wire = srv.plan.pack(grads_like(params, 1))
    srv.push_packed(0, wire)
    d = srv.pull_delta(0, (-1,) * 3)     # bootstrap: everything arrives
    assert not d.full and set(d.shards) == {0, 1, 2}
    layout = srv.plan.wire_layout()
    buf = jnp.zeros((layout.total_rows, wf.WIRE_LANES), layout.dtype)
    for j, r in zip(d.shards, d.regions):
        s = layout.shard_row_start[j]
        buf = buf.at[s:s + r.shape[0]].set(r)
    assert jnp.array_equal(buf, srv.pull_packed())
    d2 = srv.pull_delta(0, d.versions)   # current vector -> empty delta
    assert d2.empty and not d2.full and d2.versions == d.versions
    assert jnp.array_equal(buf, srv.pull_packed())   # nothing moved
    srv.stop()


def test_partial_delta_ships_only_advanced_shards_and_counts_bytes():
    params = make_params()
    srv = make_sharded(params, n_shards=4)
    layout = srv.plan.wire_layout()
    d0 = srv.pull_delta(0, (-1,) * 4)
    WIRE.reset()
    buf = jnp.ones((layout.shard_rows[1], wf.WIRE_LANES), layout.dtype)
    srv.push_packed_shard(0, 1, buf)
    d = srv.pull_delta(0, d0.versions)
    assert d.shards == (1,) and not d.full
    itemsize = jnp.dtype(layout.dtype).itemsize
    full_bytes = layout.total_rows * wf.WIRE_LANES * itemsize
    shipped = layout.shard_rows[1] * wf.WIRE_LANES * itemsize
    ev = WIRE.snapshot()
    assert ev["delta_bytes_tx"] == shipped
    assert ev["full_pull_bytes_avoided"] == full_bytes - shipped
    assert shipped < full_bytes
    # patched buffer == full pull
    wire = srv.pull_packed()
    s = layout.shard_row_start[1]
    assert jnp.array_equal(d.regions[0], wire[s:s + layout.shard_rows[1]])
    srv.stop()


def test_delta_vector_mismatch_falls_back_to_full():
    params = make_params()
    srv = make_sharded(params, n_shards=2)
    for bad in (None, (0,), (0, 0, 0), (99, 99)):
        d = srv.pull_delta(0, bad)
        assert d.full and set(d.shards) == {0, 1}, bad
    srv.stop()


def test_delta_arity_mismatch_after_live_reshard_full_fallback():
    """Regression for the PR-5 fallback x live reshard: a client whose
    version vector is S-long against a server that genuinely migrated
    to S' shards gets a FULL snapshot at the new epoch, and the
    reassembled buffer is bitwise the server's packed state."""
    params = make_params()
    srv = make_sharded(params, n_shards=2)
    d0 = srv.pull_delta(0, (-1, -1))
    assert d0.epoch == 0
    srv.push_packed(0, srv.plan.pack(grads_like(params, 4)))
    srv.reshard(3)                       # live migration, epoch 0 -> 1
    d = srv.pull_delta(0, d0.versions)   # stale 2-vector vs 3 shards
    assert d.full and d.epoch == 1
    assert len(d.versions) == 3 and set(d.shards) == {0, 1, 2}
    layout = srv.plan.wire_layout()
    buf = np.zeros((layout.total_rows, wf.WIRE_LANES), layout.dtype)
    for j, r in zip(d.shards, d.regions):
        s = layout.shard_row_start[j]
        buf[s:s + r.shape[0]] = r
    np.testing.assert_array_equal(buf, np.asarray(srv.pull_packed()))
    # the new vector is current: the next delta is empty, same epoch
    d2 = srv.pull_delta(0, d.versions)
    assert d2.empty and not d2.full and d2.epoch == 1
    srv.stop()


def test_worker_delta_pull_loop_matches_full_pulls():
    """A PSWorker with delta_pull=True trains bitwise-identically to
    one doing full packed pulls (single worker = deterministic)."""
    from repro.ps.step import packed_step
    from repro.ps.worker import PSWorker, run_cluster

    def run(delta):
        params = make_params()
        srv = make_sharded(params, n_workers=1, n_shards=2,
                           policy="dssp")

        def tree_step(p, batch):
            grads = jax.tree_util.tree_map(lambda x: x * 0 + 0.01, p)
            return grads, {"loss": 1.0}

        def batches():
            while True:
                yield None

        w = PSWorker(0, srv, packed_step(srv.plan, tree_step), batches(),
                     5, delta_pull=delta)
        run_cluster(srv, [w], timeout=120.0)
        out = np.asarray(srv.pull_packed())
        srv.stop()
        return out

    np.testing.assert_array_equal(run(False), run(True))


# ========================================================= frame codec
def test_pull_delta_frame_roundtrip():
    f = wf.Frame(kind=wf.MSG_PULL_DELTA, worker=3,
                 versions=(0, -1, 7, 123456789))
    out = wf.decode_frame(wf.encode_frame(f))
    assert out.kind == wf.MSG_PULL_DELTA
    assert out.versions == (0, -1, 7, 123456789)
    assert out.worker == 3


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_delta_frame_roundtrip(dtype):
    dt = wf.np_wire_dtype(dtype if isinstance(dtype, str)
                          else np.dtype(dtype).name)
    rng = np.random.RandomState(0)
    r1 = rng.randn(8, wf.WIRE_LANES).astype(dt)
    r2 = rng.randn(16, wf.WIRE_LANES).astype(dt)
    f = wf.Frame(kind=wf.MSG_DELTA, worker=1, flags=wf.FLAG_FULL,
                 versions=(4, 5, 6), delta=[(0, r1), (2, r2)])
    out = wf.decode_frame(wf.encode_frame(f))
    assert out.kind == wf.MSG_DELTA
    assert out.versions == (4, 5, 6)
    assert out.flags & wf.FLAG_FULL
    assert [s for s, _ in out.delta] == [0, 2]
    np.testing.assert_array_equal(out.delta[0][1], r1)
    np.testing.assert_array_equal(out.delta[1][1], r2)


def test_delta_frame_empty_and_malformed():
    f = wf.Frame(kind=wf.MSG_DELTA, versions=(1, 2), delta=[])
    out = wf.decode_frame(wf.encode_frame(f))
    assert out.versions == (1, 2) and list(out.delta) == []
    # truncated body -> FrameError, not garbage
    good = wf.encode_frame(wf.Frame(
        kind=wf.MSG_DELTA, versions=(3,),
        delta=[(0, np.zeros((8, wf.WIRE_LANES), np.float32))]))
    header, _ = wf.decode_header(good[:wf.HEADER_SIZE])
    with pytest.raises(wf.FrameError, match="DELTA"):
        wf.decode_body(header, good[wf.HEADER_SIZE:-16])
    # a PULL_DELTA body that is not an int64 vector is rejected at the
    # header (payload_len % 8 != 0)
    bad = bytearray(wf.encode_frame(wf.Frame(kind=wf.MSG_PULL_DELTA,
                                             versions=(1,))))
    bad_header = wf.HEADER.pack(wf.FRAME_MAGIC, wf.FRAME_VERSION,
                                wf.MSG_PULL_DELTA, 0, 0, -1, -1, 0, 0,
                                5, 0.0)
    with pytest.raises(wf.FrameError, match="PULL_DELTA"):
        wf.decode_header(bad_header)
    del bad


def test_delta_over_tcp_and_shard_routed_endpoint_rejects():
    from repro.transport import PSServerEndpoint, make_transport
    params = make_params()
    srv = make_sharded(params, n_workers=1, n_shards=2)
    layout = srv.plan.wire_layout()
    ep = PSServerEndpoint(srv)
    tp = make_transport("tcp", n_workers=1)
    tp.serve(ep)
    try:
        c = tp.connect(0)
        c.hello()
        d = c.pull_delta((-1, -1))
        assert isinstance(d, DeltaPull) and set(d.shards) == {0, 1}
        host = np.zeros((layout.total_rows, wf.WIRE_LANES), np.float32)
        for j, r in zip(d.shards, d.regions):
            s = layout.shard_row_start[j]
            host[s:s + r.shape[0]] = r
        np.testing.assert_array_equal(host, np.asarray(srv.pull_packed()))
        d2 = c.pull_delta(d.versions)
        assert d2.empty
        dbad = c.pull_delta((0,))           # wrong arity -> full fallback
        assert dbad.full and set(dbad.shards) == {0, 1}
        c.bye()
        c.close()
    finally:
        srv.stop()
        tp.shutdown()
    # shard-routed endpoints refuse delta pulls (the vector spans all
    # shards); exercised at the dispatch layer directly
    srv2 = make_sharded(params, n_workers=1, n_shards=2)
    ep2 = PSServerEndpoint(srv2, shards={0})
    reply = ep2.handle(wf.Frame(kind=wf.MSG_PULL_DELTA, worker=0,
                                versions=(0, 0)))
    assert reply.kind == wf.MSG_ERR and "full-store" in reply.error
    srv2.stop()


# =========================================== snapshot-cache regression
def test_snapshot_cache_key_always_matches_contents_under_hammer():
    """PR-5 satellite: hammer push+pull concurrently and assert the
    version-keyed snapshot cache never serves bytes that disagree with
    its key.

    lr=1, momentum=0, grads=-1 make every applied update add exactly
    +1.0 to each element of its shard, so shard j's region must read
    ``initial + key[j]`` whenever the cache claims version key[j].
    """
    params = {"a": jnp.zeros((64, 512), jnp.float32),
              "b": jnp.zeros((64, 512), jnp.float32)}
    srv = make_sharded(params, n_workers=4, n_shards=2, momentum=0.0,
                       lr=1.0, coalesce=2, coalesce_wait=0.0)
    layout = srv.plan.wire_layout()
    wire_g = srv.plan.pack(jax.tree_util.tree_map(
        lambda p: -jnp.ones_like(p), params))
    stop = threading.Event()
    errors = []

    def pusher(w):
        i = 0
        while not stop.is_set() and i < 25:
            srv.push_packed(w, wire_g)
            i += 1

    def puller():
        while not stop.is_set():
            srv.pull_packed(0)
            with srv._snap_lock:
                key, wire = srv._snap_key, srv._snap_wire
            if key is None:
                continue
            host = np.asarray(wire)
            for j in range(2):
                s = layout.shard_row_start[j]
                region = host[s:s + layout.shard_rows[j]]
                # the cache key leads with the reshard epoch; the
                # per-shard versions follow
                expect = float(key[1 + j])
                if not np.allclose(region, expect):
                    errors.append((key, j, float(region.flat[0])))
                    stop.set()
                    return

    threads = [threading.Thread(target=pusher, args=(w,))
               for w in range(4)] + [threading.Thread(target=puller)
                                     for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads[:4]:
        t.join(timeout=180.0)
    stop.set()
    for t in threads[4:]:
        t.join(timeout=10.0)
    srv.stop()
    assert not errors, f"cache key disagreed with contents: {errors[:3]}"
    assert srv.shard_versions() == [100, 100]   # nothing lost


def test_snapshot_cache_never_goes_backwards():
    """The dominance guard: installing an older per-shard snapshot over
    a newer cached one is refused even when another shard advanced."""
    params = make_params()
    srv = make_sharded(params, n_shards=2)
    srv.pull_packed(0)
    with srv._snap_lock:
        # keys lead with the reshard epoch; versions follow
        srv._snap_key = (0, 5, 5)    # pretend a fresher pull landed
        marker = srv._snap_wire
    # a would-be install with versions (6, 4) at the same epoch is
    # newer on shard 0 but older on shard 1 -> must NOT replace (5, 5)
    key = (0, 6, 4)
    with srv._snap_lock:
        cached = srv._snap_key
        if cached is None or key[0] > cached[0] or (
                key[0] == cached[0]
                and all(n >= c for n, c in zip(key[1:], cached[1:]))
                and any(n > c for n, c in zip(key[1:], cached[1:]))):
            srv._snap_key = key
    assert srv._snap_key == (0, 5, 5)
    assert srv._snap_wire is marker
    srv.stop()


# ===================================================== e2e acceptance
@pytest.mark.parametrize("transport", ["tcp"])
def test_e2e_dssp_coalesced_delta_matches_plain_packed(transport):
    """Acceptance: a 4-worker DSSP run through repro.api with
    ps.coalesce=4 + wire.delta_pull over a real process transport
    reaches the same final-loss tolerance as the plain packed threads
    path, while the server-side perfcount shows coalescing engaged
    (batched launches < one per push per shard)."""
    from repro.api import (DataSpec, ModelSpec, OptimizerSpec, RunSpec,
                           ServerSpec, SyncSpec, TransportSpec, WireSpec,
                           build_session)

    common = dict(
        model=ModelSpec(arch="xlstm-125m", smoke=True),
        data=DataSpec(seq_len=32, global_batch=4),
        optimizer=OptimizerSpec(lr=0.02),
        sync=SyncSpec(mode="dssp", s_lower=0, s_upper=3))
    baseline = RunSpec(
        ps=ServerSpec(kind="sharded", shards=2, workers=4,
                      apply="fused"),
        wire=WireSpec(format="packed"), **common)
    # a wide flusher linger so homogeneous workers' near-simultaneous
    # pushes reliably land in one window on a loaded CI runner (the
    # default 50 ms is tuned for latency, not determinism)
    tentpole = RunSpec(
        ps=ServerSpec(kind="sharded", shards=2, workers=4,
                      apply="fused", coalesce=4,
                      coalesce_wait_ms=500.0),
        wire=WireSpec(format="packed", delta_pull=True),
        transport=TransportSpec(kind=transport), **common)

    with build_session(baseline) as session:
        base = session.run(32)

    WIRE.reset()
    with build_session(tentpole) as session:
        got = session.run(32)
    ev = WIRE.snapshot()

    assert got["pushes"] >= 4 and got["applied_updates"] > 0
    assert np.isfinite(got["final_loss"])
    # same model/data/steps: final losses agree to the asynchrony
    # tolerance (same bound as the existing e2e transport test)
    assert abs(got["final_loss"] - base["final_loss"]) <= \
        max(0.15 * abs(base["final_loss"]), 0.15), (base, got)
    # coalescing engaged: the server did FEWER batched-apply launches
    # than one per shard per push (shards x pushes), because concurrent
    # workers folded into shared windows; and delta pulls shipped
    # fewer bytes than pushes x full snapshots would have.
    shards = 2
    assert ev["apply_launches_saved"] > 0, ev
    assert ev["pallas_calls"] + ev["apply_launches_saved"] >= \
        got["applied_updates"]
    assert ev["pallas_calls"] < shards * got["pushes"], ev
