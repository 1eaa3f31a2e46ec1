"""The control — the reference put in the program's place and computed
in float8, one step below the configuration's bfloat16 — has to come
out not correct: every cell's limits reject it.  At smoke size here;
``control.py`` reads it on the chip at the cells' own sizes."""

import pytest

from chip import control
from chip.conftest import smoke


@pytest.mark.parametrize("workload", ["danube-spmd.adamw",
                                      "danube-ps.solo"])
def test_control_fails_the_limits(workload):
    _, _, cfg, traffic, limits = smoke(workload)
    gaps = control.control_gaps(cfg, traffic, seed=5, variants=("fp8",))
    gaps = gaps["fp8"]
    assert set(gaps) == set(limits)
    assert any(gaps[k] > limits[k] for k in limits), (gaps, limits)
