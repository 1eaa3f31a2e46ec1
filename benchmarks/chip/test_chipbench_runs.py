"""Whole runs of every cell at smoke size on the CPU (the chip check
skipped): the program's first steps match the reference, the gate keeps
its guarantee, the window counts its work and compiles nothing."""

import pytest

from chip.conftest import TWO_WORKERS, run_smoke


@pytest.mark.parametrize("workload,traffic", [
    ("danube-spmd.adamw", {}),
    ("danube-ps.solo", {}),
    ("danube-ps.solo", TWO_WORKERS),
])
def test_cell_runs_correct(workload, traffic):
    out = run_smoke(workload, **traffic)
    checks = out["checks"]
    assert out["correct"], checks
    assert out["compiles_in_window"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    # float32 at smoke size: the program and the reference agree to
    # rounding, far inside the chip's bfloat16 limits
    for name in ("loss_gap", "grad1_gap", "change_gap"):
        assert checks[name]["value"] < 1e-4, (name, checks[name])
    if workload.startswith("danube-ps"):
        assert checks["lost_pushes"]["value"] == 0
        assert checks["over_bound"]["value"] == 0
