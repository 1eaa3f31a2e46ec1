"""DSSP adapted to SPMD TPU pods (DESIGN.md §3.2-3.3).

On a pod there is no parameter server: the gradient all-reduce *is* the
synchronization.  The paper's degree of freedom — how stale may a
contribution be before the system forces a sync — maps to two JAX-native
mechanisms, both bounded by [s_L, s_U] so Theorem 2 carries over:

1. **Delayed gradient application** (within-pod / cross-replica).  The
   gradient computed at step ``t`` enters a ring buffer and is *applied*
   at step ``t + d`` with ``d ∈ [s_L, s_U]`` chosen by the host-side
   controller.  Because step ``t``'s parameter update no longer depends
   on step ``t``'s collective, the runtime can overlap that collective
   with the forward/backward of the following step(s) — the SPMD analogue
   of "the fast worker keeps iterating instead of waiting".  ``d`` is a
   *traced scalar*: changing it between steps does not recompile.

2. **Dynamic-period cross-pod averaging** (local SGD).  Pods are the
   paper's workers; every pod takes ``k`` local steps between cross-pod
   averages, ``k ∈ [s_L, s_U]`` re-chosen at run time from per-pod step
   telemetry via the *same* Algorithm-2 controller.  Implemented with
   ``shard_map`` manual over the 'pod' axis (params carry per-pod values
   between syncs) while 'data'/'model' stay under GSPMD.

The host-side ``DsspScheduleController`` turns measured step/collective
times into (d, k) using the paper's simulated-timestamp argmin.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp


Tree = Any


# ------------------------------------------------------- delayed gradients
class PipelineState(NamedTuple):
    buffer: Tree          # stacked pending grads, leading dim = depth
    step: jax.Array       # int32 global step


def init_pipeline(grads_like: Tree, depth: int) -> PipelineState:
    """depth = s_U + 1 ring slots (delay d uses slot (step - d) % depth)."""
    if depth < 1:
        raise ValueError("depth must be >= 1")
    buf = jax.tree_util.tree_map(
        lambda g: jnp.zeros((depth,) + g.shape, g.dtype), grads_like)
    return PipelineState(buffer=buf, step=jnp.zeros((), jnp.int32))


def pipeline_specs(grad_specs: Tree, depth: int) -> Tree:
    """Buffer shards like the gradient with an unsharded ring dim."""
    from jax.sharding import PartitionSpec as P

    def add_dim(spec):
        return P(None, *spec)

    buf = jax.tree_util.tree_map(
        add_dim, grad_specs, is_leaf=lambda x: isinstance(x, P))
    return PipelineState(buffer=buf, step=P())


def push_pop(state: PipelineState, grads: Tree, delay: jax.Array,
             ) -> Tuple[Tree, jax.Array, PipelineState]:
    """Write ``grads`` into the ring; read the gradient from ``delay``
    steps ago.  Returns (delayed_grads, valid_scale, new_state) where
    ``valid_scale`` is 0.0 for the warm-up steps that have no gradient to
    apply yet (t < d) and 1.0 afterwards.

    delay == 0 reproduces BSP exactly (reads what it just wrote).
    """
    depth = jax.tree_util.tree_leaves(state.buffer)[0].shape[0]
    delay = jnp.clip(jnp.asarray(delay, jnp.int32), 0, depth - 1)
    w = state.step % depth
    buf = jax.tree_util.tree_map(
        lambda b, g: jax.lax.dynamic_update_index_in_dim(
            b, g.astype(b.dtype), w, 0), state.buffer, grads)
    r = (state.step - delay) % depth
    delayed = jax.tree_util.tree_map(
        lambda b: jax.lax.dynamic_index_in_dim(b, r, 0, keepdims=False), buf)
    valid = (state.step >= delay).astype(jnp.float32)
    return delayed, valid, PipelineState(buffer=buf, step=state.step + 1)


# ------------------------------------------------------ host-side controller
@dataclasses.dataclass
class DsspScheduleController:
    """Chooses the delay ``d`` and cross-pod period ``k`` at run time.

    The paper's Algorithm-2 recipe — predict near-future intervals from
    the most recent observed ones, then pick the bound in [s_L, s_U] that
    minimizes predicted waiting — specialized to the SPMD streams:

    * ``delay()``: the compute stream (interval = step time) must not
      consume the collective stream's result before it lands; the minimal
      non-waiting delay is ceil(t_coll / t_step) on the *predicted*
      intervals (IntervalEstimator: 'last' = paper, 'ema'/'median'
      robust), clamped to [s_L, s_U].
    * ``period(pod_times)``: pods are the paper's workers; Algorithm 2's
      simulate+argmin runs verbatim on the fastest/slowest pod's
      predicted step intervals to choose extra local steps before the
      next cross-pod average.
    """

    s_lower: int
    s_upper: int
    estimator: str = "last"

    def __post_init__(self):
        from repro.core.controller import IntervalEstimator
        self._est = IntervalEstimator(mode=self.estimator)
        self.history = []

    def observe(self, step_time: float, collective_time: float) -> None:
        """Feed one step's measured (or roofline-derived) timings."""
        self._est.observe(0, max(1e-12, step_time))
        self._est.observe(1, max(0.0, collective_time))
        self.history.append((step_time, collective_time))

    def delay(self) -> int:
        t_step = self._est.predict(0)
        t_coll = self._est.predict(1)
        if t_step is None or t_coll is None:
            return self.s_lower
        d = -(-t_coll // t_step)                     # ceil division
        return int(min(self.s_upper, max(self.s_lower, d)))

    def period(self, pod_step_times) -> int:
        """Cross-pod averaging period from per-pod step times (Alg. 2)."""
        from repro.core.controller import (optimal_extra_iterations,
                                           simulate_push_times)
        fast, slow = min(pod_step_times), max(pod_step_times)
        r_max = self.s_upper - self.s_lower
        sim_fast = simulate_push_times(0.0, fast, r_max)
        sim_slow = simulate_push_times(0.0, slow, r_max, lead=1)
        r = optimal_extra_iterations(sim_fast, sim_slow)
        return int(min(self.s_upper, max(self.s_lower, self.s_lower + r)))


# --------------------------------------------------- cross-pod local SGD
def cross_pod_sync(tree: Tree, mesh: jax.sharding.Mesh,
                   specs: Tree) -> Tree:
    """Average a pytree across the 'pod' mesh axis with shard_map manual
    over 'pod' only ('data'/'model' shardings pass through untouched)."""

    def avg(t):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, "pod"), t)

    fn = jax.shard_map(avg, mesh=mesh, in_specs=(specs,), out_specs=specs,
                       axis_names=frozenset({"pod"}), check_vma=False)
    return fn(tree)


def local_sgd_step(train_step: Callable, sync_params: Callable,
                   ) -> Callable:
    """Wrap a per-pod train step with conditional cross-pod averaging.

    ``do_sync`` is a traced bool scalar: the host flips it every k-th step
    (k from DsspScheduleController.period()) without recompiling.
    """

    def step(params, opt_state, pipeline, batch, delay, do_sync):
        params, opt_state, pipeline, metrics = train_step(
            params, opt_state, pipeline, batch, delay)
        params = jax.lax.cond(do_sync, sync_params,
                              lambda t: t, params)
        return params, opt_state, pipeline, metrics

    return step
