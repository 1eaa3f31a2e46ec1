"""Shared pieces of the benchmark's CPU tests: its cells cut to the
program's smoke size (the model zoo's ``smoke`` configuration), so a
whole run fits a test."""

import argparse
import json
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: h2o-danube's smoke configuration in the program's registry.
SMOKE_MODEL = dict(hidden_size=64, intermediate_size=160,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, num_hidden_layers=2, vocab_size=256,
                   sliding_window=16, rope_theta=10000.0,
                   rms_norm_eps=1e-6, torch_dtype="float32")
SMOKE_SEQ = 32


#: Two workers of unequal speed on the parameter server: the harness's
#: path for several workers (shard-by-shard arrival order, the gate's
#: bound), which no cell on the chip runs yet.
TWO_WORKERS = {"workers": 2, "speed_factors": [1.0, 2.0], "batch": 2}


def smoke(workload: str, **traffic_over):
    """(bench, work, cfg, traffic, limits) of a cell at smoke size."""
    from chip import run
    bench, work, cfg, traffic, limits = run.load_cell(ROOT, workload)
    cfg = dict(cfg, **SMOKE_MODEL)
    cfg["program"] = dict(cfg["program"], smoke=True)
    traffic = dict(traffic, seq_len=SMOKE_SEQ, **traffic_over)
    return bench, work, cfg, traffic, limits


def run_smoke(workload: str, seed: int = 2**31 + 17, seconds: float = 1.0,
              **traffic_over):
    """A whole run of a cell at smoke size on the CPU, the chip check
    skipped; returns the result object."""
    import jax

    from chip import run
    bench, work, cfg, traffic, limits = smoke(workload, **traffic_over)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return run.run_cell(bench, work, cfg, traffic, limits, args,
                        jax.devices(), require_tpu=False)


@pytest.fixture
def workloads():
    with open(ROOT / "BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]]
