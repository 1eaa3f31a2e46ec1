"""Sharded parameter server: partitioned packed store + per-shard gating.

See ``plan.py`` for the shard plan format, ``server.py`` for the
threaded runtime, ``apply.py`` for the apply's settings and coalescing
window, and ``simulator.py`` for the virtual-time instrument.
"""

from repro.ps.sharded.apply import (DEFAULT_COALESCE_WAIT_S,
                                    CoalesceWindow, ServerOptimizer)
from repro.ps.sharded.plan import (LeafSlice, Shard, ShardPlan,
                                   WireLayout, build_shard_plan)
from repro.ps.sharded.server import ShardedParameterServer
from repro.ps.sharded.simulator import (ShardedPSSimulator,
                                        hot_shard_service,
                                        run_sharded_policy)

__all__ = [
    "LeafSlice", "Shard", "ShardPlan", "WireLayout", "build_shard_plan",
    "ServerOptimizer", "CoalesceWindow", "DEFAULT_COALESCE_WAIT_S",
    "ShardedParameterServer",
    "ShardedPSSimulator", "run_sharded_policy", "hot_shard_service",
]
