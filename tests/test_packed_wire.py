"""Packed wire format: layout round-trips, zero-repack server contract,
fused compression kernels, dtype preservation.

Acceptance probes (ISSUE 2):
  * one packed push performs ZERO host-side per-leaf concatenations /
    packs on the server (perfcount probe),
  * at most one ``pallas_call`` per shard for apply plus one for
    compression,
  * packed-path numerics match the tree path on the same push sequence,
  * bf16 trees round-trip without the silent f32 bounce (satellite),
  * the fused-mode piece cache is rebuilt OUTSIDE the shard lock
    (satellite).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy, make_policy_factory
from repro.kernels import ref
from repro.kernels.fused_compress import fused_int8_ef, fused_topk_ef
from repro.kernels.fused_update import pack_shard, unpack_shard
from repro.perfcount import WIRE
from repro.ps.server import ParameterServer, ServerOptimizer
from repro.ps.sharded import ShardedParameterServer, build_shard_plan
from repro.ps.worker import PSWorker, run_cluster


def _tree(seed=0, shapes=((40, 16), (16,), (8, 8), ()), dtype=np.float32):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(np.asarray(rng.randn(*s), dtype))
            for i, s in enumerate(shapes)}


def _grads_like(tree, seed):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            np.asarray(rng.randn(*p.shape), p.dtype)), tree)


def _max_diff(a, b):
    return max(float(jnp.abs(jnp.asarray(x, jnp.float32)
                             - jnp.asarray(y, jnp.float32)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------ wire layout
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8])
def test_pack_unpack_roundtrip_bitwise(n_shards):
    tree = _tree()
    plan = build_shard_plan(tree, n_shards)
    back = plan.unpack(plan.pack(tree))
    assert _max_diff(tree, back) == 0.0


def test_pack_unpack_roundtrip_with_split_leaves():
    tree = {"big": jnp.arange(1024 * 8, dtype=jnp.float32).reshape(1024, 8),
            "small": jnp.arange(4, dtype=jnp.float32)}
    plan = build_shard_plan(tree, 4)
    assert any(not sl.whole for s in plan.shards for sl in s.slices)
    assert _max_diff(tree, plan.unpack(plan.pack(tree))) == 0.0
    assert _max_diff(tree, plan.assemble_packed(
        plan.split_packed(tree))) == 0.0


def test_pack_unpack_roundtrip_with_empty_shards():
    tree = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    plan = build_shard_plan(tree, 8)
    assert any(len(s.slices) == 0 for s in plan.shards)
    layout = plan.wire_layout()
    assert any(r == 0 for r in layout.shard_rows)
    assert _max_diff(tree, plan.unpack(plan.pack(tree))) == 0.0


@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_shard_wire_views_equal_packed_pieces(n_shards):
    """The packed wire's per-shard row ranges hold exactly what
    ``pack_shard_pieces`` would build from the tree split — the view IS
    the shard's wire payload."""
    tree = _tree(seed=3)
    plan = build_shard_plan(tree, n_shards)
    wire = plan.pack(tree)
    for j in range(n_shards):
        view = plan.shard_wire(wire, j)
        built = plan.pack_shard_pieces(plan.shard_pieces(tree, j), j)
        assert view.shape == built.shape
        assert float(jnp.abs(view - built).max()) == 0.0
        for a, b in zip(plan.shard_pieces(tree, j),
                        plan.shard_pieces_from_wire(view, j)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert float(jnp.abs(a - b).max()) == 0.0


def test_wire_rows_are_lane_and_tile_aligned():
    plan = build_shard_plan(_tree(), 3)
    layout = plan.wire_layout()
    for rows in layout.shard_rows:
        assert rows % 8 == 0
    assert layout.total_rows == sum(layout.shard_rows)
    # every element of every leaf has exactly one place on the wire
    assert sum(n for segs in layout.leaf_segments
               for _, n in segs) == layout.total_elems


def test_pack_unpack_jittable():
    tree = _tree(seed=1)
    plan = build_shard_plan(tree, 2)
    f = jax.jit(lambda t: plan.unpack(plan.pack(t)))
    assert _max_diff(tree, f(tree)) == 0.0


# ------------------------------------------------------------ dtype fix
def test_pack_shard_preserves_uniform_bf16():
    """Satellite regression: bf16 leaves used to bounce through f32 on
    pack/unpack; a uniform-dtype shard must round-trip bitwise in its
    own dtype."""
    leaves = [jnp.asarray(np.random.RandomState(0).randn(33, 7),
                          jnp.bfloat16),
              jnp.asarray(np.random.RandomState(1).randn(130),
                          jnp.bfloat16)]
    buf = pack_shard(leaves)
    assert buf.dtype == jnp.bfloat16
    back = unpack_shard(buf, [x.shape for x in leaves],
                        [x.dtype for x in leaves])
    for a, b in zip(leaves, back):
        assert b.dtype == jnp.bfloat16
        assert jnp.all(a == b)


def test_pack_shard_mixed_dtypes_promote_to_f32():
    leaves = [jnp.ones((4, 4), jnp.bfloat16), jnp.ones((8,), jnp.float32)]
    assert pack_shard(leaves).dtype == jnp.float32


def test_plan_wire_dtype_follows_tree():
    bf = _tree(dtype=np.float32)
    bf = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), bf)
    plan = build_shard_plan(bf, 2)
    wire = plan.pack(bf)
    assert wire.dtype == jnp.bfloat16
    back = plan.unpack(wire)
    for a, b in zip(jax.tree_util.tree_leaves(bf),
                    jax.tree_util.tree_leaves(back)):
        assert b.dtype == jnp.bfloat16
        assert jnp.all(a == b)


def test_bf16_fused_server_keeps_bf16_store():
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), _tree())
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 2, apply_mode="fused")
    for st in server.shards:
        assert st._packed_p.dtype == jnp.bfloat16
        assert st._packed_m.dtype == jnp.bfloat16
    g = _grads_like(params, seed=5)
    server.push_packed(0, server.plan.pack(g))
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(server.params))


# -------------------------------------------------- packed server contract
def test_packed_push_matches_tree_push():
    """Acceptance: packed-path numerics == tree path on the same push
    sequence (momentum SGD, several shards)."""
    params = _tree()
    tree_srv = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1, momentum=0.9), 2, 3,
        apply_mode="tree")
    pk_srv = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1, momentum=0.9), 2, 3,
        apply_mode="fused")
    for i in range(12):
        g = _grads_like(params, seed=i)
        tree_srv.push(i % 2, g)
        pk_srv.push_packed(i % 2, pk_srv.plan.pack(g))
    assert _max_diff(tree_srv.params, pk_srv.params) < 1e-5
    assert tree_srv.shard_versions() == pk_srv.shard_versions()


def test_packed_push_zero_server_repacks():
    """Acceptance probe: one packed push = zero per-leaf concats, zero
    pack/unpack events, and at most one kernel launch per shard."""
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 3, apply_mode="fused")
    wire = server.plan.pack(_grads_like(params, seed=0))
    server.push_packed(0, wire)          # warm up
    WIRE.reset()
    server.push_packed(0, wire)
    snap = WIRE.snapshot()
    assert snap["leaf_concats"] == 0, snap
    assert snap["packs"] == 0 and snap["unpacks"] == 0, snap
    assert snap["pallas_calls"] <= server.n_shards, snap


def test_packed_push_with_compression_one_extra_launch_per_shard():
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 3, apply_mode="fused",
        wire_compression="int8")
    wire = server.plan.pack(_grads_like(params, seed=0))
    server.push_packed(0, wire)
    WIRE.reset()
    server.push_packed(0, wire)
    snap = WIRE.snapshot()
    assert snap["leaf_concats"] == 0 and snap["packs"] == 0, snap
    assert snap["pallas_calls"] <= 2 * server.n_shards, snap


def test_pull_packed_version_keyed_snapshot_cache():
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 3, apply_mode="fused")
    w1 = server.pull_packed(0)
    assert server.pull_packed(0) is w1      # cache hit, same versions
    server.push_packed(0, server.plan.pack(_grads_like(params, seed=1)))
    w2 = server.pull_packed(0)
    assert w2 is not w1
    assert _max_diff(server.plan.unpack(w2), server.params) < 1e-6


def test_packed_api_requires_fused_store():
    server = ShardedParameterServer(
        _tree(), make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 2, apply_mode="tree")
    with pytest.raises(ValueError):
        server.pull_packed(0)
    with pytest.raises(ValueError):
        server.push_packed(0, server.plan.pack(_tree()))


def test_push_packed_rejects_mismatched_wire():
    """Regression: Python slicing clamps, so an undersized wire buffer
    would silently hand trailing shards an empty region and DROP their
    updates — it must be rejected instead."""
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 1, 3, apply_mode="fused")
    rows = server.plan.wire_layout().total_rows
    with pytest.raises(ValueError):
        server.push_packed(0, jnp.zeros((rows - 8, 512)))
    with pytest.raises(ValueError):
        server.push_packed(0, [jnp.zeros((8, 512))])   # wrong count
    mono = ParameterServer(params, make_policy("asp"),
                           ServerOptimizer(lr=0.1), 1,
                           apply_mode="packed")
    assert mono.plan.wire_layout().total_rows == 8
    with pytest.raises(ValueError):
        mono.push_packed(0, jnp.zeros((16, 512)))


def test_tree_pull_unpacks_outside_shard_lock(monkeypatch):
    """Satellite: after an apply, a fused-mode pull rebuilds the piece
    cache WITHOUT holding the shard lock — a concurrent push must be
    able to take the lock mid-pull."""
    from repro.ps.sharded.plan import ShardPlan
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 2, 1, apply_mode="fused")
    server.push_packed(0, server.plan.pack(_grads_like(params, seed=0)))
    st = server.shards[0]
    assert st._pieces is None               # cache invalidated

    lock_free_during_unpack = threading.Event()
    orig = ShardPlan.shard_pieces_from_wire

    def probed(self, buf, j, dtype=None):
        # While the pull is unpacking, the shard lock must be free.
        got = st.cond.acquire(timeout=5.0)
        if got:
            st.cond.release()
            lock_free_during_unpack.set()
        return orig(self, buf, j, dtype)

    monkeypatch.setattr(ShardPlan, "shard_pieces_from_wire", probed)
    server.pull(0)
    assert lock_free_during_unpack.is_set()
    # second pull is a cache hit (no new unpack)
    monkeypatch.setattr(ShardPlan, "shard_pieces_from_wire", orig)
    WIRE.reset()
    server.pull(0)
    assert WIRE.snapshot()["unpacks"] == 0


def test_pull_cache_not_installed_if_version_moved(monkeypatch):
    """The outside-lock unpack must not clobber a newer version's state:
    if a push lands mid-unpack, the stale piece cache is discarded."""
    from repro.ps.sharded.plan import ShardPlan
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), 2, 1, apply_mode="fused")
    wire0 = server.plan.pack(_grads_like(params, seed=0))
    server.push_packed(0, wire0)
    st = server.shards[0]
    orig = ShardPlan.shard_pieces_from_wire

    def racing(self, buf, j, dtype=None):
        out = orig(self, buf, j, dtype)
        monkeypatch.setattr(ShardPlan, "shard_pieces_from_wire", orig)
        server.push_packed(1, wire0)                # version moves mid-pull
        return out

    monkeypatch.setattr(ShardPlan, "shard_pieces_from_wire", racing)
    stale = server.pull(0)
    assert st._pieces is None                       # stale cache discarded
    fresh = server.pull(0)
    assert _max_diff(fresh, server.params) == 0.0
    assert _max_diff(stale, fresh) > 0.0            # pull saw the old version


# ------------------------------------------------------ monolithic packed
def test_monolithic_packed_matches_tree():
    params = _tree()
    mono = ParameterServer(params, make_policy("ssp", staleness=2),
                           ServerOptimizer(lr=0.1, momentum=0.9), 3)
    packed = ParameterServer(params, make_policy("ssp", staleness=2),
                             ServerOptimizer(lr=0.1, momentum=0.9), 3,
                             apply_mode="packed")
    for i in range(30):
        g = _grads_like(params, seed=100 + i)
        mono.push(i % 3, g)
        packed.push_packed(i % 3, packed.plan.pack(g))
    assert mono.version == packed.version == 30
    assert _max_diff(mono.params, packed.params) < 1e-5
    assert mono.metrics.staleness_hist == packed.metrics.staleness_hist


def test_monolithic_packed_tree_push_packs_once():
    params = _tree()
    server = ParameterServer(params, make_policy("asp"),
                             ServerOptimizer(lr=0.1), 1,
                             apply_mode="packed")
    g = _grads_like(params, seed=0)
    server.push(0, g)                       # warm up
    WIRE.reset()
    server.push(0, g)
    snap = WIRE.snapshot()
    assert snap["packs"] == 1 and snap["pallas_calls"] == 1, snap


def test_monolithic_packed_guards():
    server = ParameterServer(_tree(), make_policy("asp"),
                             ServerOptimizer(lr=0.1), 1)
    with pytest.raises(ValueError):
        server.push_packed(0, jnp.zeros((8, 512)))
    with pytest.raises(ValueError):
        server.pull_packed(0)
    with pytest.raises(ValueError):
        ParameterServer(_tree(), make_policy("asp"),
                        ServerOptimizer(lr=0.1), 1, apply_mode="bogus")


# ------------------------------------------------------ fused compression
@pytest.mark.parametrize("rows", [8, 24, 64])
def test_fused_int8_ef_matches_ref(rows):
    rng = np.random.RandomState(rows)
    g = jnp.asarray(rng.randn(rows, 512).astype(np.float32))
    e = jnp.asarray(rng.randn(rows, 512).astype(np.float32) * 0.01)
    dq, er = fused_int8_ef(g, e, interpret=True)
    dqr, err_ = ref.fused_int8_ef_ref(g, e)
    np.testing.assert_allclose(dq, dqr, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(er, err_, atol=1e-6, rtol=1e-6)
    # error feedback identity: decoded + residual == input + carried err
    np.testing.assert_allclose(np.asarray(dq) + np.asarray(er),
                               np.asarray(g) + np.asarray(e), atol=1e-5)


@pytest.mark.parametrize("fraction", [0.02, 0.05, 0.25])
def test_fused_topk_ef_matches_ref_and_keeps_fraction(fraction):
    rng = np.random.RandomState(7)
    g = jnp.asarray(rng.randn(32, 512).astype(np.float32))
    e = jnp.zeros((32, 512), jnp.float32)
    dq, er = fused_topk_ef(g, e, fraction=fraction, interpret=True)
    dqr, err_ = ref.fused_topk_ef_ref(g, e, fraction=fraction)
    np.testing.assert_allclose(dq, dqr, atol=1e-6)
    np.testing.assert_allclose(er, err_, atol=1e-6)
    kept = float((np.asarray(dq) != 0).mean())
    assert fraction * 0.8 <= kept <= fraction * 1.5, kept
    np.testing.assert_allclose(np.asarray(dq) + np.asarray(er),
                               np.asarray(g), atol=1e-5)


def test_fused_compress_empty_and_bad_shapes():
    z = jnp.zeros((0, 512))
    assert fused_int8_ef(z, z)[0].shape == (0, 512)
    with pytest.raises(ValueError):
        fused_int8_ef(jnp.zeros((7, 512)), jnp.zeros((7, 512)))
    with pytest.raises(ValueError):
        fused_topk_ef(jnp.zeros((8, 512)), jnp.zeros((16, 512)))
    with pytest.raises(ValueError):
        fused_topk_ef(jnp.zeros((8, 512)), jnp.zeros((8, 512)),
                      fraction=0.0)


def test_wire_compression_error_feedback_converges():
    """Error feedback keeps the compression bias from accumulating: the
    sum of decoded pushes tracks the sum of raw gradients."""
    params = _tree()
    raw = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.05), 1, 2, apply_mode="fused")
    comp = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.05), 1, 2, apply_mode="fused",
        wire_compression="int8")
    for i in range(16):
        w = raw.plan.pack(_grads_like(params, seed=i))
        raw.push_packed(0, w)
        comp.push_packed(0, w)
    drift = _max_diff(raw.params, comp.params)
    assert 0.0 < drift < 0.05, drift


# ------------------------------------------------------ end-to-end worker
def _make_problem(seed=0, dim=8, n=512):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, 1).astype(np.float32)
    x = rng.randn(n, dim).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n, 1).astype(np.float32)
    return x, y


def _batches(x, y, worker, n_workers, bs=32, seed=0):
    sx, sy = x[worker::n_workers], y[worker::n_workers]
    rng = np.random.RandomState(seed + worker)
    while True:
        idx = rng.randint(0, len(sx), size=bs)
        yield sx[idx], sy[idx]


def test_packed_worker_trains_through_sharded_server():
    """PSWorker(wire_format='packed') + jitted unpack-grad-pack step
    converges through the packed hot path."""
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = ShardedParameterServer(
        params, make_policy_factory("dssp", n_workers=3, s_lower=1,
                                    s_upper=5),
        lambda: ServerOptimizer(lr=0.05), 3, 2, apply_mode="fused")
    plan = server.plan

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    @jax.jit
    def step(wire, batch):
        p = plan.unpack(wire)
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        return plan.pack(grads), {"loss": loss}

    workers = [PSWorker(w, server, step, _batches(x, y, w, 3), 30,
                        wire_format="packed")
               for w in range(3)]
    run_cluster(server, workers, timeout=120.0)
    pred = x @ server.params["w"] + server.params["b"]
    final = float(jnp.mean((pred - y) ** 2))
    assert final < 0.25 * float(jnp.mean(y ** 2))
    assert server.metrics.total_pushes == 3 * 30
    assert all(v == 3 * 30 for v in server.shard_versions())
