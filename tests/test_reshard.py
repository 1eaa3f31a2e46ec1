"""Live resharding (``repro.ft.reshard`` + the server protocol).

Covers the acceptance surface:

* the migration map is a partition: every real element of the old
  layout is covered exactly once, destinations never overlap, and
  ``migrate`` is a bitwise, invertible permutation of the packed
  parameter/momentum buffers;
* gradient translation through the map equals packing the same tree
  under the new plan directly;
* an in-heap fused server reshards S -> S' (up and down) with params
  bitwise-preserved, ``server.version`` continuous, and training after
  the swap matching a never-resharded reference;
* a push racing the migration parks on the retired shard and replays
  exactly once (``WIRE.reshard_parked == WIRE.reshard_replayed``), a
  stale-epoch push is translated, an evicted/unknown epoch bounces
  with the retryable "resync" error, and a gate waiter stranded on an
  abandoned old shard is released;
* a tcp client observes the epoch bump, falls back to a full pull,
  and its old-layout pushes keep landing;
* the headline e2e: a 2-worker DSSP tcp run through ``repro.api``
  reshards S=4 -> S'=6 mid-run (``ft.reshard_round`` trigger) with a
  serving replica attached — every iteration completes, zero pushes
  lost or double-applied, zero staleness violations.
"""

from __future__ import annotations

import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy_factory
from repro.ft.reshard import (
    MigrationMap,
    build_migration,
    equalized_counts,
    live_reshard,
    spread_versions,
)
from repro.perfcount import WIRE
from repro.ps.sharded import ServerOptimizer
from repro.ps.sharded.plan import build_shard_plan
from repro.ps.sharded.server import ShardedParameterServer
from repro.wireformat import WIRE_LANES, FrameError

warnings.filterwarnings("ignore", category=DeprecationWarning)
pytestmark = pytest.mark.filterwarnings("ignore::UserWarning")


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


def make_server(params, *, n_workers=2, n_shards=4, policy="asp",
                momentum=0.9, **pkw):
    return ShardedParameterServer(
        params, make_policy_factory(policy, n_workers=n_workers, **pkw),
        lambda: ServerOptimizer(lr=0.05, momentum=momentum),
        n_workers, n_shards)


def max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) if x.shape
               else abs(float(x) - float(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ============================================================ map units
@pytest.mark.parametrize("s_old,s_new", [(4, 6), (6, 4), (1, 5), (3, 1)])
def test_migration_map_partitions_every_element(s_old, s_new):
    params = make_params()
    old = build_shard_plan(params, s_old)
    new = build_shard_plan(params, s_new)
    mig = build_migration(old, new)
    assert isinstance(mig, MigrationMap)
    total = old.wire_layout().total_elems
    assert sum(m.size for m in mig.moves) == total
    # destinations are disjoint: sort per new shard and check no overlap
    for k in range(s_new):
        spans = sorted((m.new_off, m.new_off + m.size)
                       for m in mig.moves if m.new_shard == k)
        for (_, hi), (lo2, _) in zip(spans, spans[1:]):
            assert hi <= lo2
    # sources are disjoint too (nothing copied twice)
    for j in range(s_old):
        spans = sorted((m.old_off, m.old_off + m.size)
                       for m in mig.moves if m.old_shard == j)
        for (_, hi), (lo2, _) in zip(spans, spans[1:]):
            assert hi <= lo2
    assert "->" in mig.describe()


def test_migrate_is_bitwise_and_invertible():
    params = make_params()
    old = build_shard_plan(params, 4)
    new = build_shard_plan(params, 6)
    fwd = build_migration(old, new)
    bwd = build_migration(new, old)
    bufs = old.shard_wires(old.pack(params))     # zero-padded regions
    there = fwd.migrate(bufs)
    # forward == packing the same tree under the new plan directly
    want = new.shard_wires(new.pack(params))
    for got, exp in zip(there, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # and back again: a permutation, bitwise
    back = bwd.migrate(there)
    for got, exp in zip(back, bufs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_migrate_grads_equals_new_plan_repack():
    params = make_params()
    g = grads_like(params, 3)
    old = build_shard_plan(params, 3)
    new = build_shard_plan(params, 5)
    mig = build_migration(old, new)
    translated = mig.migrate_grads(old.shard_wires(old.pack(g)))
    want = new.shard_wires(new.pack(g))
    for got, exp in zip(translated, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # moves_from partitions the move list by source shard
    assert sorted(
        (m for j in range(3) for m in mig.moves_from(j)),
        key=lambda m: (m.old_shard, m.old_off)) == sorted(
        mig.moves, key=lambda m: (m.old_shard, m.old_off))


def test_build_migration_rejects_mismatched_trees():
    a = build_shard_plan(make_params(), 2)
    b = build_shard_plan({"x": jnp.zeros((7, 5))}, 2)
    with pytest.raises(ValueError, match="same tree"):
        build_migration(a, b)


def test_spread_versions_is_sum_preserving():
    for total, n in [(0, 3), (7, 3), (24, 4), (100, 6), (5, 8)]:
        out = spread_versions(total, n)
        assert sum(out) == total and len(out) == n
        assert max(out) - min(out) <= 1      # balanced


def test_equalized_counts_takes_crossshard_minimum():
    got = equalized_counts([{0: 5, 1: 3}, {0: 4, 1: 3}, {0: 9, 1: 2}])
    assert got == {0: 4, 1: 2}
    assert equalized_counts([]) == {}


# ======================================================= in-heap server
class TestLiveReshard:
    def test_reshard_preserves_params_and_version_sum(self):
        params = make_params()
        srv = make_server(params)
        ref = make_server(params)
        wires = [srv.plan.pack(grads_like(params, s)) for s in range(3)]
        for i, w in enumerate(wires):
            srv.push_packed(i % 2, w)
            ref.push_packed(i % 2, w)
        v_sum = srv.version
        before = srv.params

        assert live_reshard(srv, 6) is True
        assert srv.reshard_epoch == 1 and srv.n_shards == 6
        assert len(srv.shard_versions()) == 6
        assert srv.version == v_sum           # the logical clock held
        assert max_leaf_diff(before, srv.params) == 0.0

        # a same-arity call is a no-op (and does not bump the epoch)
        assert srv.reshard(6) is False
        assert srv.reshard_epoch == 1

        # down again: still bitwise vs the never-resharded reference
        assert srv.reshard(3) is True
        assert srv.reshard_epoch == 2 and srv.n_shards == 3
        assert max_leaf_diff(ref.params, srv.params) == 0.0
        srv.stop(), ref.stop()

    def test_training_after_reshard_matches_reference(self):
        params = make_params()
        srv = make_server(params)
        ref = make_server(params)
        g_pre = srv.plan.pack(grads_like(params, 1))
        srv.push_packed(0, g_pre)
        ref.push_packed(0, g_pre)
        srv.reshard(6)
        g_post = grads_like(params, 2)
        srv.push_packed(1, srv.plan.pack(g_post))   # new layout
        ref.push_packed(1, ref.plan.pack(g_post))
        assert max_leaf_diff(ref.params, srv.params) == 0.0
        srv.stop(), ref.stop()

    def test_stale_epoch_push_is_translated_not_lost(self):
        params = make_params()
        srv = make_server(params)
        ref = make_server(params)
        old_plan = srv.plan
        srv.reshard(6)
        WIRE.reset()
        g = grads_like(params, 5)
        # packed under the RETIRED plan, declared as epoch 0 — exactly
        # what a client that has not re-pulled yet sends
        srv.push_packed(0, old_plan.pack(g), epoch=0)
        ref.push_packed(0, ref.plan.pack(g))
        assert WIRE.snapshot()["reshard_translated"] == 1
        assert max_leaf_diff(ref.params, srv.params) == 0.0
        # shape inference maps an old-layout buffer onto its epoch even
        # without an explicit epoch (the in-heap caller path)
        g2 = grads_like(params, 6)
        srv.push_packed(1, old_plan.pack(g2))
        ref.push_packed(1, ref.plan.pack(g2))
        assert max_leaf_diff(ref.params, srv.params) == 0.0
        srv.stop(), ref.stop()

    def test_unknown_epoch_push_bounces_retryable(self):
        srv = make_server(make_params())
        wire = srv.plan.pack(grads_like(make_params(), 0))
        with pytest.raises(ValueError, match="resync"):
            srv.push_packed(0, wire, epoch=7)
        srv.stop()

    def test_push_racing_migration_parks_and_replays_exactly_once(self):
        params = make_params()
        srv = make_server(params)
        ref = make_server(params)
        g_pre = grads_like(params, 1)
        g_mid = grads_like(params, 2)
        g_post = grads_like(params, 3)
        srv.push_packed(0, srv.plan.pack(g_pre))
        mid_wire = srv.plan.pack(g_mid)
        WIRE.reset()
        fired = []

        def hook(shard_index: int) -> None:
            # After shard 1's state is copied out, shards 0-1 are
            # retired (their applies must PARK) while 2-3 are still
            # live — the push below straddles the migration.
            if shard_index == 1 and not fired:
                fired.append(True)
                srv.push_packed(1, mid_wire)

        assert srv.reshard(6, _mid_hook=hook) is True
        ev = WIRE.snapshot()
        assert fired, "mid-migration hook never fired"
        assert ev["reshard_parked"] == 2          # shards 0 and 1 parked
        assert ev["reshard_replayed"] == ev["reshard_parked"]
        srv.push_packed(0, srv.plan.pack(g_post))
        for w, g in ((0, g_pre), (1, g_mid), (0, g_post)):
            ref.push_packed(w, ref.plan.pack(g))
        # the replay folds momentum host-side over moved segments only;
        # same f32 arithmetic as the kernel, so the tolerance is tiny
        assert max_leaf_diff(ref.params, srv.params) < 1e-6
        srv.stop(), ref.stop()

    def test_gate_waiter_on_abandoned_shard_is_released(self):
        params = make_params()
        srv = make_server(params, n_workers=2, policy="bsp")
        wire = srv.plan.pack(grads_like(params, 0))
        done = threading.Event()

        def blocked_push():
            srv.push_packed(0, wire)   # BSP: blocks until worker 1 pushes
            done.set()

        t = threading.Thread(target=blocked_push, daemon=True)
        t.start()
        assert not done.wait(0.3), "BSP barrier did not block"
        srv.reshard(6)                 # abandons the old shards' barriers
        assert done.wait(30.0), "waiter stranded on an abandoned shard"
        t.join(timeout=10.0)
        # the NEW barriers are mutually consistent: a full round releases
        threads = [threading.Thread(
            target=srv.push_packed,
            args=(w, srv.plan.pack(grads_like(params, w))))
            for w in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60.0)
        assert not any(th.is_alive() for th in threads)
        srv.stop()

    def test_delta_pull_carries_epoch_and_full_fallback(self):
        params = make_params()
        srv = make_server(params)
        d0 = srv.pull_delta(0, (-1,) * 4)
        assert d0.epoch == 0
        srv.push_packed(0, srv.plan.pack(grads_like(params, 1)))
        srv.reshard(6)
        d = srv.pull_delta(0, d0.versions)       # stale 4-vector
        assert d.full and d.epoch == 1
        assert len(d.versions) == 6
        layout = srv.plan.wire_layout()
        buf = np.zeros((layout.total_rows, WIRE_LANES), layout.dtype)
        for j, r in zip(d.shards, d.regions):
            s = layout.shard_row_start[j]
            buf[s:s + r.shape[0]] = r
        np.testing.assert_array_equal(buf, np.asarray(srv.pull_packed()))
        srv.stop()

    def test_reshard_rejects_tree_mode_and_bad_arity(self):
        srv2 = make_server(make_params())
        with pytest.raises(ValueError, match="n_shards"):
            srv2.reshard(0)
        srv2.stop()


# =============================================================== tcp
def test_tcp_client_observes_epoch_and_stale_push_lands():
    from repro.transport import PSServerEndpoint, make_transport
    params = make_params()
    srv = make_server(params, n_workers=1, n_shards=2)
    old_plan = srv.plan
    ep = PSServerEndpoint(srv)
    tp = make_transport("tcp", n_workers=1)
    tp.serve(ep)
    try:
        c = tp.connect(0)
        c.hello()
        assert c.reshard_epoch == 0
        d0 = c.pull_delta((-1, -1))
        assert d0.epoch == 0

        srv.reshard(3)
        # the stale vector falls back to a full pull at the new epoch
        d = c.pull_delta(d0.versions)
        assert d.full and d.epoch == 1 and len(d.versions) == 3
        # a push still packed under the OLD layout (the client has not
        # rebuilt yet, so its frame carries epoch 0) is translated
        ref = make_server(params, n_workers=1, n_shards=2)
        g = grads_like(params, 9)
        assert c.push_packed(np.asarray(old_plan.pack(g))) is True
        ref.push_packed(0, ref.plan.pack(g))
        assert max_leaf_diff(ref.params, srv.params) == 0.0
        ref.stop()
        # adopting the new epoch, new-layout pushes flow normally
        c.reshard_epoch = 1
        assert c.push_packed(
            np.asarray(srv.plan.pack(grads_like(params, 10)))) is True
        # an epoch the server never issued bounces with the retryable
        # "resync" error a worker turns into a re-pull + retry
        c.reshard_epoch = 9
        with pytest.raises(FrameError, match="resync"):
            c.push_packed(np.asarray(srv.plan.pack(grads_like(params, 11))))
        c.reshard_epoch = 1
        c.bye()
        c.close()
    finally:
        srv.stop()
        tp.shutdown()


# ========================================================= session API
def test_session_manual_reshard_trigger(tmp_path):
    from repro.api import build_session
    spec = {
        "model": {"arch": "xlstm-125m", "smoke": True},
        "ps": {"kind": "sharded", "shards": 2, "workers": 1,
               "apply": "fused"},
        "wire": {"format": "packed", "delta_pull": True},
        "sync": {"mode": "asp"},
        "transport": {"kind": "tcp"},
    }
    with build_session(spec, external_workers=True) as session:
        session.start()
        assert session.reshard(3) is True
        assert session.reshard(3) is False       # already there
        assert session.server.n_shards == 3


# ===================================================== e2e acceptance
def test_e2e_dssp_tcp_live_reshard_with_replica():
    """Acceptance: 2-worker DSSP over tcp through ``repro.api``, the
    server live-reshards S=4 -> S'=6 at push round 6 while a serving
    replica stays subscribed — every iteration completes, the loss
    trajectory spans the migration, zero pushes are lost or
    double-applied (parked == replayed, push count conserved), and the
    replica sees zero staleness violations."""
    from repro.api import (DataSpec, ModelSpec, RunSpec, ServeSpec,
                           ServerSpec, SyncSpec, TransportSpec, WireSpec,
                           build_session)
    from repro.api import FtSpec

    spec = RunSpec(
        model=ModelSpec(arch="xlstm-125m", smoke=True),
        data=DataSpec(seq_len=32, global_batch=4),
        ps=ServerSpec(kind="sharded", shards=4, workers=2,
                      apply="fused"),
        sync=SyncSpec(mode="dssp", s_lower=0, s_upper=3),
        wire=WireSpec(format="packed", delta_pull=True),
        transport=TransportSpec(kind="tcp"),
        ft=FtSpec(reshard_shards=6, reshard_round=6),
        serve=ServeSpec(replicas=1, requests=4, request_every_ms=100.0,
                        start_at_version=1, prompt_len=8, max_new=4,
                        max_batch=4, staleness_bound=6))
    WIRE.reset()
    with build_session(spec) as session:
        m = session.run(steps=24)
        server = session.server
        assert server.n_shards == 6, "reshard trigger never fired"
        assert server.reshard_epoch == 1
    ev = WIRE.snapshot()
    # zero lost / double-applied: whatever parked replayed exactly once
    assert ev["reshard_parked"] == ev["reshard_replayed"]
    assert m["iterations_done"] == 24
    assert m["pushes"] == 24                  # every push accounted for
    assert m["final_loss"] is not None and np.isfinite(m["final_loss"])
    losses = [x for x in (m["first_loss"], m["final_loss"])
              if x is not None]
    assert all(np.isfinite(x) for x in losses)
    serve = m["serve"]
    assert serve["requests"] == 4
    assert serve["violations"] == 0
