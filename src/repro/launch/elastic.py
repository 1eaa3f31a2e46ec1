"""Elastic scaling: adapt a running job to a changed resource shape.

Two independent elasticity paths live in this repo, one per engine:

**SPMD pipeline** (this module): node loss (or growth) flow —
  1. the job restarts with however many devices survive,
  2. ``elastic_mesh(n)`` builds the largest (data, model) mesh that fits,
  3. ``remesh`` device_puts the checkpointed state under the new mesh's
     shardings (host RAM is the transfer buffer — the same path a real
     multi-host restore uses per-host shards for),
  4. the data pipeline re-shards itself by (host_index, n_hosts) — batch
     order is a pure function of the step, so no samples are lost or
     duplicated (data/synthetic.py),
  5. DSSP's controller re-learns step intervals within a few steps
     (the paper's adaptivity argument, §III.B).

**Parameter-server layer**: elasticity has two axes —
  * *worker membership* is handled in-place: workers join/leave the
    per-shard staleness trackers at runtime
    (``ps/sharded/server.py``, ``add_worker``/``remove_worker``) and the barrier gate re-derives
    its group from the live membership;
  * *shard arity* is handled by **live resharding**
    (``repro.ft.reshard`` + ``ShardedParameterServer.reshard``): the
    packed parameter+momentum regions migrate S -> S' one shard at a
    time under the per-shard locks while training continues, and
    clients resync through the version-delta full-pull fallback.
    ``reshard_ps`` below is the launch-layer entry point.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: F401

from repro.launch.mesh import elastic_mesh
from repro.models import registry
from repro.models.params import spec_tree
from repro.models.sharding import rules_for_mesh


def remesh(tree: Any, spec: Any, mesh: jax.sharding.Mesh) -> Any:
    """device_put a pytree under new shardings (specs pytree-aligned)."""
    def put(x, s):
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree_util.tree_map(
        put, tree, spec, is_leaf=lambda x: x is None)


def rescale_params(cfg, params: Any, n_devices: int,
                   model_parallel: int = 16,
                   ) -> Tuple[Any, jax.sharding.Mesh]:
    """Reshard ``params`` for a cluster that now has ``n_devices``."""
    mesh = elastic_mesh(n_devices, model_parallel=model_parallel)
    rules = rules_for_mesh(mesh)
    specs = spec_tree(registry.param_defs(cfg), rules)
    return remesh(params, specs, mesh), mesh


def reshard_ps(server, n_shards: int) -> bool:
    """PS-side elasticity: migrate a live sharded server to
    ``n_shards`` partitions without stopping training.

    Thin launch-layer alias of ``repro.ft.reshard.live_reshard`` — the
    full protocol (migration map, parked pushes, epoch bump, client
    resync) is documented there.  Returns False when the server is
    already at that arity.
    """
    from repro.ft.reshard import live_reshard
    return live_reshard(server, n_shards)
