"""Sharded parameter server: plan, server, simulator, elasticity.

Covers the subsystem's contract:
  * ShardPlan split/assemble round-trip, balance, oversized-leaf splitting,
  * the server's p and m after a fixed push sequence equal a plain numpy
    damped-momentum loop at every shard count, coalesced or not, and the
    damping scale is 1/(1+staleness),
  * global gating gives the same apply/drop sequence at any shard count,
  * S=1 simulator equivalence with PSSimulator (same metrics),
  * per-shard staleness stays within the policy bound on EVERY shard,
  * pushes to distinct shards genuinely overlap (no global lock),
  * elastic membership (join/leave mid-run) never deadlocks any shard's
    barrier and keeps per-shard staleness profiles consistent,
  * per-shard wire compression: the identity compressor is bitwise a
    plain push, int8 is close (the make_compressor("none") error-state
    fix too).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy, make_policy_factory
from repro.optim.compression import make_compressor
from repro.ps.sharded import (ServerOptimizer, ShardedParameterServer,
                              build_shard_plan, hot_shard_service,
                              run_sharded_policy)
from repro.ps.simulator import run_policy
from repro.ps.step import packed_step
from repro.ps.worker import PSWorker, run_cluster


def _tree(seed=0, shapes=((40, 16), (16,), (8, 8), ())):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(np.asarray(rng.randn(*s), np.float32))
            for i, s in enumerate(shapes)}


def _grads_like(tree, seed):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(np.asarray(rng.randn(*p.shape), np.float32)),
        tree)


def _max_diff(a, b):
    return max(float(jnp.abs(x - y).max()) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------------ plan
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8])
def test_plan_split_assemble_roundtrip(n_shards):
    tree = _tree()
    plan = build_shard_plan(tree, n_shards)
    back = plan.assemble(plan.split(tree))
    assert _max_diff(tree, back) == 0.0
    assert plan.total_size == sum(
        int(np.prod(x.shape)) if x.shape else 1
        for x in jax.tree_util.tree_leaves(tree))


def test_plan_splits_oversized_leaves_and_balances():
    tree = {"big": jnp.zeros((1024, 8)), "small": jnp.zeros((4,))}
    plan = build_shard_plan(tree, 4)
    # the 8192-element leaf dominates: without splitting one shard would
    # hold >99% of the weights
    assert plan.imbalance() < 1.2
    assert any(not sl.whole for shard in plan.shards for sl in shard.slices)
    back = plan.assemble(plan.split(tree))
    assert _max_diff(tree, back) == 0.0


def test_plan_no_split_when_disabled():
    tree = {"big": jnp.zeros((1024, 8)), "small": jnp.zeros((4,))}
    plan = build_shard_plan(tree, 4, split_oversized=False)
    assert all(sl.whole for shard in plan.shards for sl in shard.slices)


def test_plan_deterministic():
    tree = _tree()
    a = build_shard_plan(tree, 3)
    b = build_shard_plan(tree, 3)
    assert a.shards == b.shards


# ----------------------------------------------------- apply arithmetic
def _momenta(server):
    """The momentum pytree, unpacked from the shards' packed stores."""
    plan = server.plan
    return plan.assemble([plan.shard_pieces_from_wire(st._packed_m, j)
                          for j, st in enumerate(server.shards)])


@pytest.mark.parametrize("coalesce", [1, 3])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_server_p_and_m_match_numpy_momentum_loop(n_shards, coalesce):
    """Three workers push the same gradient concurrently each round; the
    server's p and m equal a plain numpy damped-momentum loop over the
    same pushes (identical gradients within a round make the fold order
    within a coalesced window immaterial)."""
    lr, beta, rounds, workers = 0.1, 0.9, 4, 3
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp", n_workers=workers),
        lambda: ServerOptimizer(lr=lr, momentum=beta), workers, n_shards,
        coalesce=coalesce, coalesce_wait=5.0 if coalesce > 1 else None)
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    m = {k: np.zeros_like(v) for k, v in p.items()}
    for r in range(rounds):
        g = _grads_like(params, seed=300 + r)
        wire = server.plan.pack(g)
        threads = [threading.Thread(target=server.push_packed,
                                    args=(w, wire))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
        for _ in range(workers):
            for k in p:
                m[k] = np.float32(beta) * m[k] + np.asarray(g[k])
                p[k] = p[k] - np.float32(lr) * m[k]
    assert server.shard_versions() == [rounds * workers] * n_shards
    got_p, got_m = server.snapshot(), _momenta(server)
    for k in p:
        np.testing.assert_allclose(np.asarray(got_p[k]), p[k],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_m[k]), m[k],
                                   rtol=1e-5, atol=1e-6)
    server.stop()


def test_staleness_damping_scales_the_fold():
    """Damping weighs a push by 1/(1+staleness) inside the fused apply:
    ASP with two workers, pushes at staleness 1, 2 and 0."""
    params = {"w": jnp.zeros((4,), jnp.float32)}
    server = ShardedParameterServer(
        params, make_policy_factory("asp", n_workers=2),
        lambda: ServerOptimizer(lr=1.0, momentum=0.5,
                                staleness_damping=True), 2, 1)
    g = server.plan.pack({"w": jnp.ones((4,), jnp.float32)})
    p = m = 0.0
    for worker, staleness in ((0, 1), (0, 2), (1, 0)):
        server.push_packed(worker, g)
        m = 0.5 * m + 1.0 / (1.0 + staleness)
        p = p - m
        np.testing.assert_allclose(np.asarray(server.snapshot()["w"]),
                                   np.full(4, p, np.float32), rtol=1e-6)
    assert server.metrics.staleness_hist == {1: 1, 2: 1, 0: 1}
    server.stop()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_global_gating_drops_and_applies_as_at_one_shard(n_shards):
    """In gating='global' the gate's decision governs every shard's
    apply: with the backup-workers policy (which DROPS straggler
    gradients) a partitioned store applies and drops exactly the pushes
    the one-shard server does, and ends at the same weights."""
    params = _tree()

    def run(s):
        server = ShardedParameterServer(
            params, make_policy_factory("backup", n_workers=2, backups=1),
            lambda: ServerOptimizer(lr=0.1), 2, s, gating="global")
        applied = []
        for i in range(10):
            server.push_packed(i % 2, server.plan.pack(
                _grads_like(params, seed=200 + i)))
            applied.append(server.metrics.applied_updates)
        server.stop()
        return applied, server.metrics.dropped_updates, server.snapshot()

    one, many = run(1), run(n_shards)
    assert one[0] == many[0]
    assert one[1] == many[1] > 0
    assert _max_diff(one[2], many[2]) < 1e-6


def test_fused_apply_handles_empty_shards():
    """Regression: n_shards > piece count yields empty shards; a
    zero-row pallas_call would reject its tile — apply must no-op."""
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    server = ShardedParameterServer(params, make_policy_factory("asp"),
                                    lambda: ServerOptimizer(lr=0.1), 2, 8)
    assert any(len(s.slices) == 0 for s in server.plan.shards)
    g = server.plan.pack(jax.tree_util.tree_map(jnp.ones_like, params))
    for i in range(4):
        server.push_packed(i % 2, g)
    assert server.shard_versions() == [4] * 8
    assert _max_diff(server.params["w"],
                     jnp.ones((4, 4)) - 0.1 * 4 * jnp.ones(())) < 1e-5


def test_fused_update_shard_matches_per_leaf_kernel():
    """The public batched kernel API (one pallas_call over the packed
    shard) is numerically identical to per-leaf fused_update."""
    from repro.kernels.fused_update import fused_update, fused_update_shard
    leaves = list(jax.tree_util.tree_leaves(_tree()))
    ms = [jnp.ones_like(x) * 0.1 for x in leaves]
    gs = list(jax.tree_util.tree_leaves(_grads_like(_tree(), seed=7)))
    po, mo = fused_update_shard(leaves, ms, gs, lr=0.05, beta=0.9,
                                scale=0.5, interpret=True)
    for p, m, g, pn, mn in zip(leaves, ms, gs, po, mo):
        pe, me = fused_update(p, m, g, lr=0.05, beta=0.9, scale=0.5,
                              interpret=True)
        assert float(jnp.abs(pn - pe).max()) < 1e-6
        assert float(jnp.abs(mn - me).max()) < 1e-6
    assert fused_update_shard([], [], [], lr=0.05) == ([], [])


def test_ps_package_import_stays_kernel_free():
    """Importing repro.ps must not drag in the Pallas kernel stack."""
    import subprocess
    import sys
    code = ("import sys; import repro.ps; "
            "sys.exit(1 if any(m.startswith('repro.kernels') "
            "for m in sys.modules) else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_identity_compressor_roundtrips_through_shards():
    params = _tree()
    servers = [ShardedParameterServer(params, make_policy_factory("asp"),
                                      lambda: ServerOptimizer(lr=0.1), 2, 2,
                                      wire_compression=comp)
               for comp in (None, "none", "int8")]
    plain, ident, int8 = servers
    for i in range(8):
        wire = plain.plan.pack(_grads_like(params, seed=i))
        for s in servers:
            s.push_packed(i % 2, wire)
    assert _max_diff(plain.params, ident.params) == 0.0
    # int8 is lossy-but-error-fed-back: close, not identical
    assert 0.0 < _max_diff(plain.params, int8.params) < 0.1


def test_none_compressor_error_state_is_grads_shaped():
    """Regression: make_compressor('none').init_error used to return ()."""
    g = _tree()
    c = make_compressor("none")
    err = c.init_error(g)
    assert (jax.tree_util.tree_structure(err)
            == jax.tree_util.tree_structure(g))
    g2, err2 = c.apply(g, err)
    assert _max_diff(g, g2) == 0.0
    assert (jax.tree_util.tree_structure(err2)
            == jax.tree_util.tree_structure(g))


# ------------------------------------------------- simulator equivalence
@pytest.mark.parametrize("name,kw", [
    ("bsp", {}), ("asp", {}), ("ssp", {"staleness": 3}),
    ("dssp", {"s_lower": 3, "s_upper": 15})])
def test_sim_s1_metrics_identical_to_monolithic(name, kw):
    intervals = [1.0, 1.0, 1.0, 4.0]
    mono = run_policy(make_policy(name, n_workers=4, **kw), intervals,
                      max_pushes=1500)
    s1 = run_sharded_policy(
        make_policy_factory(name, n_workers=4, **kw), intervals, 1,
        max_pushes=1500).metrics
    a, b = mono.summary(), s1.summary()
    for key in ("pushes", "applied", "total_wait", "mean_staleness",
                "max_staleness", "time", "throughput"):
        assert a[key] == b[key], (key, a[key], b[key])


@pytest.mark.parametrize("n_shards", [2, 4, 16])
@pytest.mark.parametrize("name,kw,bound", [
    ("bsp", {}, 0), ("ssp", {"staleness": 3}, 3),
    ("dssp", {"s_lower": 3, "s_upper": 15}, 15)])
def test_sim_per_shard_staleness_bounded(n_shards, name, kw, bound):
    """Acceptance: with S>1 every shard's max observed staleness stays
    within the policy bound (+1 for the at-push transient, the same
    convention the monolithic tests use)."""
    sim = run_sharded_policy(
        make_policy_factory(name, n_workers=4, **kw),
        [1.0, 1.0, 1.0, 4.0], n_shards, max_pushes=1500)
    for shard_max in sim.max_staleness_per_shard():
        assert shard_max <= bound + 1
    assert sim.metrics.total_pushes == 1500


def test_sim_hot_shard_adds_wait_but_keeps_bound():
    factory = make_policy_factory("dssp", s_lower=3, s_upper=15)
    cold = run_sharded_policy(factory, [1.0, 1.0, 1.0, 4.0], 4,
                              max_pushes=800)
    hot = run_sharded_policy(factory, [1.0, 1.0, 1.0, 4.0], 4,
                             max_pushes=800,
                             shard_service_fn=hot_shard_service(0, 0.5))
    assert hot.metrics.total_time > cold.metrics.total_time
    assert max(hot.max_staleness_per_shard()) <= 16


# -------------------------------------------------- threaded: concurrency
def test_pushes_to_distinct_shards_do_not_serialize(monkeypatch):
    """Acceptance: concurrent pushes to distinct shards overlap — with a
    global lock the in-apply concurrency gauge could never exceed 1."""
    from repro.kernels import ops as kops
    real = kops.fused_update
    gauge = {"active": 0, "max": 0}
    gauge_lock = threading.Lock()

    def slow_apply(*args, **kwargs):
        with gauge_lock:
            gauge["active"] += 1
            gauge["max"] = max(gauge["max"], gauge["active"])
        time.sleep(0.03)
        try:
            return real(*args, **kwargs)
        finally:
            with gauge_lock:
                gauge["active"] -= 1

    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.01), 3, 3)
    wires = {(w, i): server.plan.pack(_grads_like(params, seed=w * 100 + i))
             for w in range(3) for i in range(6)}
    monkeypatch.setattr(kops, "fused_update", slow_apply)

    def pusher(w):
        for i in range(6):
            server.push_packed(w, wires[w, i])

    threads = [threading.Thread(target=pusher, args=(w,)) for w in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads)
    assert gauge["max"] >= 2, (
        "shard applies never overlapped — pushes serialized globally")


# --------------------------------------------- threaded: training + elastic
def _make_problem(seed=0, dim=8, n=512):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, 1).astype(np.float32)
    x = rng.randn(n, dim).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n, 1).astype(np.float32)
    return x, y


def _step_fn(server):
    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    def step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads, {"loss": loss}

    return packed_step(server.plan, step)


def _batches(x, y, worker, n_workers, bs=32, seed=0):
    sx, sy = x[worker::n_workers], y[worker::n_workers]
    rng = np.random.RandomState(seed + worker)
    while True:
        idx = rng.randint(0, len(sx), size=bs)
        yield sx[idx], sy[idx]


def _sharded_server(params, policy_name, n_workers, n_shards, **kw):
    return ShardedParameterServer(
        params, make_policy_factory(policy_name, n_workers=n_workers, **kw),
        lambda: ServerOptimizer(lr=0.05), n_workers, n_shards)


@pytest.mark.parametrize("policy", ["bsp", "dssp"])
def test_training_converges_through_sharded_server(policy):
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _sharded_server(params, policy, 4, 3, s_lower=1, s_upper=5)
    step = _step_fn(server)
    workers = [PSWorker(w, server, step, _batches(x, y, w, 4), 30)
               for w in range(4)]
    run_cluster(server, workers, timeout=120.0)
    pred = x @ server.params["w"] + server.params["b"]
    final = float(jnp.mean((pred - y) ** 2))
    assert final < 0.25 * float(jnp.mean(y ** 2))
    assert server.metrics.total_pushes == 4 * 30
    # every shard applied every released push
    assert all(v == 4 * 30 for v in server.shard_versions())


def test_dssp_straggler_bounded_on_every_shard_threaded():
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _sharded_server(params, "dssp", 4, 4, s_lower=1, s_upper=4)
    step = _step_fn(server)
    workers = [PSWorker(w, server, step, _batches(x, y, w, 4), 30,
                        speed_factor=(6.0 if w == 3 else 1.0))
               for w in range(4)]
    run_cluster(server, workers, timeout=180.0)
    for m in server.shard_metrics():
        assert m.max_staleness <= 4 + 1


def test_worker_failure_does_not_deadlock_any_shard_barrier():
    """Satellite: remove_worker mid-run must not stall ANY shard's BSP
    barrier — the departed worker leaves every shard tracker."""
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _sharded_server(params, "bsp", 4, 3)
    step = _step_fn(server)
    workers = [PSWorker(w, server, step, _batches(x, y, w, 4), 25)
               for w in range(4)]
    workers[2].abort()
    # Sample membership while the cluster runs.  Departures sweep shards
    # in index order, so at any instant shard j's membership is a subset
    # of shard j+1's; reading in REVERSE shard order makes that chain
    # observable without racing the sweep.
    samples = []
    stop_sampling = threading.Event()

    def snapshot():
        snaps = [None] * server.n_shards
        for st in reversed(server.shards):
            with st.cond:
                snaps[st.index] = frozenset(st.tracker.workers)
        return snaps

    def sampler():
        while not stop_sampling.is_set():
            samples.append(snapshot())
            time.sleep(0.005)

    t = threading.Thread(target=sampler, daemon=True)
    t.start()
    run_cluster(server, workers, timeout=120.0)
    stop_sampling.set()
    t.join(timeout=10.0)
    done = [w.iterations_done for w in workers]
    assert done[2] == 0
    assert all(d == 25 for d in (done[0], done[1], done[3]))
    for snap in samples:
        for a, b in zip(snap, snap[1:]):
            assert a <= b, f"shard membership diverged: {snap}"
    # after the run everyone departed — trackers agree on empty
    assert all(set(p) == set() for p in server.staleness_profile().values())


def test_remove_worker_shrinks_inflight_coalesce_window():
    """Satellite: a flusher lingering for a coalesce window that counts
    a worker who just left must NOT wait out the full linger —
    ``remove_worker`` shrinks the live fill target immediately, and the
    queued payload applies exactly once."""
    params = _tree()
    server = ShardedParameterServer(
        params, make_policy_factory("asp", n_workers=2),
        lambda: ServerOptimizer(lr=0.05), 2, 2,
        coalesce=3, coalesce_wait=20.0)
    wire = server.plan.pack(_grads_like(params, 1))
    done = threading.Event()

    def push():
        server.push_packed(0, wire)   # window target is min(3, 2) = 2:
        done.set()                    # lingers for worker 1's push

    t = threading.Thread(target=push, daemon=True)
    t.start()
    assert not done.wait(0.4), "flusher did not linger for the window"
    t0 = time.monotonic()
    server.remove_worker(1)           # target shrinks to 1 -> flush now
    assert done.wait(10.0), \
        "flusher waited out the full linger after the worker left"
    t.join(timeout=10.0)
    assert time.monotonic() - t0 < 10.0
    # the parked contribution applied exactly once on every shard
    assert server.shard_versions() == [1, 1]
    for st in server.shards:
        assert st.tracker.workers == [0]
        assert st.window.pending == [] and not st.window.applying
    # the survivor keeps pushing through the (now size-1) window
    server.push_packed(0, wire)
    assert server.shard_versions() == [2, 2]
    server.stop()


def test_elastic_join_mid_run_keeps_shard_profiles_consistent():
    """Satellite: add_worker mid-run — the joiner starts at every shard's
    slowest count (no stall) and all shards agree on membership."""
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _sharded_server(params, "ssp", 2, 3, staleness=2)
    step = _step_fn(server)
    first = [PSWorker(w, server, step, _batches(x, y, w, 4), 15)
             for w in range(2)]
    run_cluster(server, first, timeout=120.0)
    server.stopped = False
    server.add_worker(2)
    # the joiner enters EVERY shard's tracker at that shard's slowest
    # count — consistent profiles, no stall on any barrier
    profile = server.staleness_profile()
    assert all(set(p) == {2} for p in profile.values())
    assert all(p[2] == 0 for p in profile.values())
    late = PSWorker(2, server, step, _batches(x, y, 2, 4), 15)
    run_cluster(server, [late], timeout=120.0)
    assert late.iterations_done == 15
    # departed again on exit — all shards agree
    assert all(set(p) == set() for p in server.staleness_profile().values())
