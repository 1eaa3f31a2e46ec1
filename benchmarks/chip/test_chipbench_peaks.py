"""The peaks table and the operation and byte counts, against counts
worked out by hand: the kernels' here, each model family's from its
``SMOKE`` sizes."""

import jax
import pytest

from chip import cells, peaks
from chip.conftest import FAMILIES

#: One causal attention of 4 query and 2 KV heads of width 2.
ATTN = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=2,
            sliding_window=None)


@pytest.fixture(params=FAMILIES)
def family(request):
    fam = cells.load_family(request.param)
    return fam, fam.sizes(fam.SMOKE["config"])


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("cpu")


@pytest.mark.parametrize("seq,window,pairs", [
    (4, None, 1 + 2 + 3 + 4),       # causal: position i sees i + 1 keys
    (6, 2, 1 + 2 + 2 + 2 + 2 + 2),  # window 2 caps every row at 2
    (3, 8, 1 + 2 + 3),              # a window longer than the sequence
])
def test_attended_pairs(seq, window, pairs):
    assert peaks.attended_pairs(seq, window) == pairs


def test_param_count(family):
    fam, m = family
    assert fam.param_count(m) == fam.SMOKE["param_count"]


def test_param_count_is_the_programs_tree(family):
    """Every parameter the program makes at the smoke size (whose
    vocabulary needs no padding rows) is counted once."""
    from repro.configs import get_smoke_config
    from repro.models import registry
    fam, m = family
    tree = jax.eval_shape(
        lambda k: registry.init_params(
            get_smoke_config(fam.SMOKE["arch"]), k),
        jax.random.PRNGKey(0))
    assert fam.param_count(m) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))


def test_train_flops_per_token(family):
    fam, m = family
    for seq, flops in fam.SMOKE["train_flops_per_token"].items():
        assert fam.train_flops_per_token(m, seq) == pytest.approx(flops)


def test_attn_fwd_and_apply_costs():
    assert peaks.has_attn(ATTN)
    assert not peaks.has_attn({"num_attention_heads": 4})
    flops, nbytes = peaks.attn_fwd_cost(2, 4, ATTN, 2)
    assert flops == 4 * 2 * 4 * 2 * 10
    # q and o: 4 heads, k and v: 2 heads; 2 rows of 4 tokens, 2 dims
    assert nbytes == 2 * 2 * 4 * 2 * (2 * 4 + 2 * 2)
    assert peaks.fused_update_cost(100, 2) == (500.0, 1000.0)


def test_roofline_names_its_bound():
    pk = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.roofline_time(1000.0, 10.0, pk) == (10.0, "compute")
    assert peaks.roofline_time(10.0, 1000.0, pk) == (100.0, "memory")
