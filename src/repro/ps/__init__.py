"""Parameter-server substrate: the threaded sharded server and its
workers (``sharded/``, ``worker.py``, ``step.py``) and the
discrete-event simulators (``simulator.py``, ``sharded/simulator.py``)."""

from repro.ps.metrics import RunMetrics, compare
from repro.ps.sharded import (
    ServerOptimizer,
    ShardedParameterServer,
    ShardedPSSimulator,
    ShardPlan,
    build_shard_plan,
    run_sharded_policy,
)
from repro.ps.simulator import (
    PSSimulator,
    constant_intervals,
    jittered_intervals,
    phase_shift_intervals,
    run_policy,
)
from repro.ps.step import loss_step, packed_step
from repro.ps.worker import PSWorker, run_cluster

__all__ = [
    "ServerOptimizer", "PSWorker", "run_cluster", "packed_step",
    "loss_step",
    "PSSimulator", "run_policy", "constant_intervals",
    "jittered_intervals", "phase_shift_intervals",
    "RunMetrics", "compare",
    "ShardedParameterServer", "ShardedPSSimulator", "ShardPlan",
    "build_shard_plan", "run_sharded_policy",
]
