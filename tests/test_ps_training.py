"""Integration: threaded PS + real jitted JAX training under every paradigm
(one-shard server, packed wire)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy_factory
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.ps.step import packed_step
from repro.ps.worker import PSWorker, run_cluster


def _make_problem(seed=0, dim=8, n=512):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, 1).astype(np.float32)
    x = rng.randn(n, dim).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n, 1).astype(np.float32)
    return x, y


def _server(params, policy_name, n_workers, **kw):
    return ShardedParameterServer(
        params, make_policy_factory(policy_name, n_workers=n_workers, **kw),
        lambda: ServerOptimizer(lr=0.05), n_workers, 1)


def _step_fn(server):
    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    # the pytree step returns (grads, aux); the packed step wraps it
    def step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads, {"loss": loss}

    return packed_step(server.plan, step)


def _batches(x, y, worker, n_workers, bs=32, seed=0):
    """Each worker iterates its own shard (data parallelism)."""
    shard_x = x[worker::n_workers]
    shard_y = y[worker::n_workers]
    rng = np.random.RandomState(seed + worker)
    while True:
        idx = rng.randint(0, len(shard_x), size=bs)
        yield shard_x[idx], shard_y[idx]


def _run(policy_name, n_workers=4, iters=30, speed_factors=None, **kw):
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _server(params, policy_name, n_workers, **kw)
    step = _step_fn(server)
    # Compile the step before the clock starts: a straggler sleeps in
    # proportion to its measured step, compile time included.
    wire = server.pull_packed()
    jax.block_until_ready(step(wire, jnp.zeros_like(wire),
                               next(_batches(x, y, 0, n_workers))))
    speed_factors = speed_factors or [1.0] * n_workers
    workers = [
        PSWorker(w, server, step,
                 _batches(x, y, w, n_workers), iters,
                 speed_factor=speed_factors[w],
                 loss_from_aux=lambda aux: float(aux["loss"]))
        for w in range(n_workers)
    ]
    run_cluster(server, workers, timeout=120.0)
    return server, x, y


def _final_loss(server, x, y):
    p = server.params
    pred = x @ p["w"] + p["b"]
    return float(jnp.mean((pred - y) ** 2))


@pytest.mark.parametrize("policy", ["bsp", "asp", "ssp", "dssp"])
def test_training_converges_under_all_paradigms(policy):
    server, x, y = _run(policy, s_lower=1, s_upper=5, staleness=2)
    initial = float(jnp.mean(y ** 2))
    final = _final_loss(server, x, y)
    assert final < 0.25 * initial, f"{policy}: {final} vs {initial}"
    assert server.version > 0
    assert server.metrics.total_pushes == 4 * 30


def test_dssp_bounded_staleness_threaded():
    server, *_ = _run("dssp", s_lower=1, s_upper=4, iters=40,
                      speed_factors=[1.0, 1.0, 1.0, 6.0])
    assert server.metrics.max_staleness <= 4 + 1


def test_heterogeneous_dssp_exploits_range():
    """Table I direction: with a straggler, DSSP runs ahead within its
    range instead of blocking at s_L.  (The *deterministic* wait-reduction
    claim is asserted in the simulator tests — wall-clock threads on one
    CPU core are too noisy for a strict inequality, so here we check the
    mechanism: credits were granted and staleness exceeded s_L, while the
    total wait stays in the same ballpark as SSP's.)"""
    sf = [1.0, 1.0, 1.0, 8.0]
    ssp_server, *_ = _run("ssp", staleness=1, iters=25, speed_factors=sf)
    dssp_server, *_ = _run("dssp", s_lower=1, s_upper=10, iters=25,
                           speed_factors=sf)
    assert dssp_server.metrics.credit_releases > 0
    assert (dssp_server.metrics.mean_staleness
            >= ssp_server.metrics.mean_staleness)
    assert (dssp_server.metrics.total_wait
            <= ssp_server.metrics.total_wait * 1.5 + 0.5)


def test_worker_failure_does_not_deadlock_bsp():
    """Fault tolerance: a worker dying mid-run leaves the barrier group."""
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _server(params, "bsp", 4)
    step = _step_fn(server)
    workers = [PSWorker(w, server, step, _batches(x, y, w, 4), 40)
               for w in range(4)]
    workers[3].abort()          # dies before its first pull
    run_cluster(server, workers, timeout=60.0)
    done = [w.iterations_done for w in workers]
    assert done[3] == 0
    assert all(d == 40 for d in done[:3])   # survivors completed


def test_elastic_worker_join():
    x, y = _make_problem()
    params = {"w": jnp.zeros((x.shape[1], 1)), "b": jnp.zeros((1,))}
    server = _server(params, "ssp", 2, staleness=2)
    step = _step_fn(server)
    first = [PSWorker(w, server, step, _batches(x, y, w, 4), 15)
             for w in range(2)]
    run_cluster(server, first, timeout=60.0)
    server.stopped = False      # resume accepting work
    server.add_worker(2)        # joins at the slowest count: no stall
    late = PSWorker(2, server, step, _batches(x, y, 2, 4), 15)
    run_cluster(server, [late], timeout=60.0)
    assert late.iterations_done == 15
