"""The cell added with the latent-attention expert family
(``moonlight-ps.solo8k``) at smoke size on the CPU: whole runs correct
on seeds small and large, the faults and the float8 control not, and the
new readers against counts worked out by hand."""

import pytest

from chip import cells, control, peaks, run
from chip.conftest import run_smoke, smoke
from chip.test_chipbench_faults import _half_batch, _state_unchanged_ps

NEW = ("moonlight-ps.solo8k",)


def _assert_correct(out):
    checks = out["checks"]
    assert out["correct"], checks
    assert out["compiles_in_window"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    # float32 at smoke size: program and reference agree to rounding
    for name in ("loss_gap", "grad1_gap", "change_gap"):
        assert checks[name]["value"] < 1e-4, (name, checks[name])


@pytest.mark.parametrize("workload", NEW)
def test_new_cell_runs_correct(workload):
    _assert_correct(run_smoke(workload))


@pytest.mark.parametrize("seed", [7, 2**31 + 4093])
def test_moonlight_runs_correct_on_other_seeds(seed):
    """The weights, the router bias balanced on them and the data all
    come from the seed: a run is correct whichever seed a check draws."""
    _assert_correct(run_smoke("moonlight-ps.solo8k", seed=seed))


@pytest.mark.parametrize("fault", [_state_unchanged_ps, _half_batch])
def test_moonlight_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run_smoke("moonlight-ps.solo8k")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", NEW)
def test_control_fails_the_new_limits(workload):
    _, _, cfg, traffic, limits = smoke(workload)
    gaps = control.control_gaps(cfg, traffic, seed=5,
                                variants=("fp8",))["fp8"]
    assert any(gaps[k] > limits[k] for k in limits), (gaps, limits)


# ------------------------------------------------------------- readers
FAM = cells.load_family("mla_moe")
M = FAM.sizes(FAM.SMOKE["config"])
PK = {"flops": 100.0, "hbm_bytes_per_s": 10.0}


def _ctx(**over):
    ctx = {"family": FAM, "model": M, "engine": "ps-threads",
           "traffic": {"batch": 2, "seq_len": 4},
           "counters": {"tokens": 8 * 5, "seconds": 2.0},
           "window_s": 2.0, "busy_s": 1.6, "n_chips": 1, "peak": PK,
           "ops": {}, "phase_seconds": {"attn_fwd": 0.5, "attn_bwd": 0.25,
                                        "moe_experts": 0.4,
                                        "moe_route": 0.2}}
    ctx.update(over)
    return ctx


def test_attention_costs_by_hand():
    """One layer's causal attention of 2 rows of 4 tokens (10 pairs a
    head), 4 heads, q/k 24 wide and v 16."""
    (ff, fb), (bf, bb) = FAM.attn_costs(2, 4, M, 2)
    assert ff == 2 * (24 + 16) * 2 * 4 * 10
    assert fb == 2 * 2 * 4 * 4 * 2 * (24 + 16)          # q, k, v, o
    assert bf == 2 * (3 * 24 + 2 * 16) * 2 * 4 * 10
    assert bb == 2 * 2 * 4 * 4 * 4 * (24 + 16)     # and dO, dq, dk, dv


def test_attention_readers_by_hand():
    """5 steps in the window: the forward runs twice a layer a step (3
    layers), the backward once; each reader divides the least time by
    its phase's seconds and names the bound."""
    (ff, fb), (bf, bb) = FAM.attn_costs(2, 4, M, 2)
    fwd = run.load_reader("mla_attn_fwd_roofline")(_ctx())
    assert fwd["calls"] == 30
    t, bound = peaks.roofline_time(30 * ff, 30 * fb, PK)
    assert fwd["value"] == pytest.approx(100 * t / 0.5)
    assert fwd["bound"] == bound
    bwd = run.load_reader("mla_attn_bwd_roofline")(_ctx())
    t, _ = peaks.roofline_time(15 * bf, 15 * bb, PK)
    assert bwd["calls"] == 15
    assert bwd["value"] == pytest.approx(100 * t / 0.25)
    dense = cells.load_family("dense")
    ctx = _ctx(family=dense, model=dense.sizes(dense.SMOKE["config"]))
    for name in ("mla_attn_fwd_roofline", "mla_attn_bwd_roofline",
                 "expert_roofline"):
        assert run.load_reader(name)(ctx) is None


def test_expert_reader_by_hand():
    """120 rows over 10 layer-steps of 8 tokens, counted while the trace
    is armed: 12 grouped matmuls a layer-step of 2 * rows * 64 * 32
    FLOPs; each reads its rows and the 4 held experts' weights and writes
    its rows."""
    from repro.obs.trace import TRACE
    from repro.perfcount import MOE
    TRACE.enable(source="test")
    MOE.add({"held_rows": 120, "tokens_routed": 80, "held_rows_max": 70})
    TRACE.disable()
    got = run.load_reader("expert_roofline")(_ctx())
    flops = 12 * 2 * 120 * 64 * 32
    nbytes = 12 * 2 * (120 * (64 + 32) + 10 * 4 * 64 * 32)
    assert FAM.expert_costs(M, 120, 10, 2) == (flops, nbytes)
    t, bound = peaks.roofline_time(flops, nbytes, PK)
    assert got["value"] == pytest.approx(100 * t / 0.4)
    assert got["bound"] == bound and got["layer_steps"] == 10
    share = run.load_reader("route_device_share")(_ctx())
    assert share == pytest.approx(100 * 0.2 / 1.6)


# ------------------------------------------------------ router balance
def _picks(params, key):
    """Picks each routed expert takes on the rows the bias is balanced
    on, per expert layer (layers, experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chip import reference as ref
    fn = FAM._fns(ref.hashable(M), "f32")
    xs = [fn["embed"](params, r) for r in FAM.balance_rows(M, key)]
    out = []
    for s, i in [(s, i) for s in FAM.STACKS
                 for i in range(params[s]["attn_norm"]["scale"].shape[0])]:
        if s == "layers":
            scores = jnp.concatenate([fn["scores"](params, jnp.int32(i), x)
                                      for x in xs])
            bias = params[s]["moe"]["router_bias"][i].astype(jnp.float32)
            _, idx = jax.lax.top_k(scores + bias, M["num_experts_per_tok"])
            out.append(np.bincount(np.asarray(idx).ravel(),
                                   minlength=M["router_outputs"]))
        xs = [fn["fwd"](params, s, jnp.int32(i), x) for x in xs]
    return np.array(out)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_bias_is_balanced(seed):
    """The weights a cell hands the program have each expert layer's
    router bias balanced: on its 8 rows of 32 tokens every one of the 16
    experts takes 8 * 32 * 3 / 16 = 48 picks, give or take the few
    tokens on the boundary that rounding the bias to bfloat16 moves
    (4).  The program's own weights (``eager``) leave some experts five
    times that and others none."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    own = FAM.init_params(M, key, jnp.bfloat16, eager=True)
    balanced = FAM.init_params(M, key, jnp.bfloat16)
    assert abs(_picks(balanced, key) - 48).max() <= 4
    assert _picks(own, key).max() > 2 * 48
    # only the bias moved
    for path, a in jax.tree_util.tree_leaves_with_path(balanced):
        b = own
        for k in path:
            b = b[k.key]
        same = bool(jnp.array_equal(a, b))
        assert same != ("router_bias" in jax.tree_util.keystr(path))
