"""Shared pieces of the benchmark's CPU tests: its cells cut to the
smoke size of their model family (``SMOKE``: the program's registry
``smoke`` configuration), so a whole run fits a test."""

import argparse
import json
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

SMOKE_SEQ = 32
#: Every model family on disk, by name.
FAMILIES = sorted(p.stem for p in (HERE / "families").glob("*.py"))


#: Two workers of unequal speed on the parameter server: the harness's
#: path for several workers (shard-by-shard arrival order, the gate's
#: bound), which no cell on the chip runs yet.
TWO_WORKERS = {"workers": 2, "speed_factors": [1.0, 2.0], "batch": 2}


def smoke(workload: str, root: Path = ROOT, **traffic_over):
    """(bench, work, cfg, traffic, limits) of a cell of the benchmark at
    ``root`` at its family's smoke size."""
    from chip import cells, run
    bench, work, cfg, traffic, limits = run.load_cell(root, workload)
    sm = cells.family_of(cfg, root / bench["paths"][0]).SMOKE
    cfg = dict(cfg, **sm["config"])
    cfg["program"] = dict(cfg["program"], arch=sm["arch"], smoke=True)
    traffic = dict(traffic, seq_len=SMOKE_SEQ, **traffic_over)
    return bench, work, cfg, traffic, limits


def run_smoke(workload: str, seed: int = 2**31 + 17, seconds: float = 1.0,
              root: Path = ROOT, **traffic_over):
    """A whole run of a cell at smoke size on the CPU, the chip check
    skipped; returns the result object."""
    import jax

    from chip import run
    bench, work, cfg, traffic, limits = smoke(workload, root,
                                              **traffic_over)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return run.run_cell(bench, work, cfg, traffic, limits, args,
                        jax.devices(), require_tpu=False,
                        here=root / bench["paths"][0])


@pytest.fixture
def workloads():
    with open(ROOT / "BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]]
