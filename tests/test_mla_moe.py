"""The DeepSeek-V3 pieces at smoke size on the CPU: the sigmoid router
with its selection-only bias, the dropless held-expert layer (under
routing skewed onto one expert, and as shares of the uncut layer), and
latent-attention decode through the cache against the full forward.

Tolerances: float32 throughout; 1e-5 relative where two formulations
of the same sums only reorder float32 additions, 1e-4 for gradients
summed through the sorted rows and for a whole model's logits out of
different attention paths (decode's grouped scores against the full
forward's)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import moe, registry, transformer

FULL = get_smoke_config("moonlight-16b-a3b")     # every expert held
CHIP = get_smoke_config("moonlight-16b-a3b-chip")  # experts 4-7 of 16


def _layer_weights(cfg, key=0):
    """One expert layer's weights (the stacked tree's first layer)."""
    params = registry.init_params(cfg, jax.random.PRNGKey(key))
    return jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])


def _x(cfg, b=2, l=24, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (b, l, cfg.d_model))


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _dense_reference(cfg, x, w):
    """The layer without any dispatch: every held expert over every
    token, weighed by the router's weight for it (zero where not
    picked), plus the shared experts."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    idx, gate = moe.route_sigmoid(cfg, xf, w["router"], w["router_bias"])
    y = _swiglu(xf, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(m.n_held):
        weight = jnp.sum(jnp.where(idx == m.held_start + e, gate, 0.0), -1)
        y = y + weight[:, None] * _swiglu(xf, w["w_gate"][e], w["w_up"][e],
                                          w["w_down"][e])
    return y.reshape(x.shape)


def test_router_bias_picks_and_does_not_weigh():
    """The experts are the top-k of sigmoid + bias; their weights are the
    unbiased sigmoid scores, renormalised, times the scale.  The seeded
    bias is nonzero and changes the picks; it gets no gradient."""
    w = _layer_weights(FULL)
    xf = _x(FULL).reshape(-1, FULL.d_model)
    bias = w["router_bias"]
    assert float(jnp.min(jnp.abs(bias))) > 0
    idx, gate = moe.route_sigmoid(FULL, xf, w["router"], bias)
    scores = 1 / (1 + np.exp(-np.asarray(xf, np.float64)
                             @ np.asarray(w["router"], np.float64)))
    k = FULL.moe.top_k
    want_idx = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    picked = np.take_along_axis(scores, np.asarray(idx), -1)
    want_gate = picked / picked.sum(-1, keepdims=True) \
        * FULL.moe.routed_scale
    np.testing.assert_allclose(np.asarray(gate), want_gate, rtol=1e-5)
    unbiased, _ = moe.route_sigmoid(FULL, xf, w["router"],
                                    jnp.zeros_like(bias))
    assert np.any(np.sort(np.asarray(unbiased), -1)
                  != np.sort(np.asarray(idx), -1))
    g = jax.grad(lambda b_: jnp.sum(moe.held_moe_block(
        FULL, _x(FULL), dict(w, router_bias=b_))[0]))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("cfg", [FULL, CHIP], ids=["all", "held4"])
def test_held_layer_matches_the_dense_reference(cfg):
    w = _layer_weights(cfg)
    x = _x(cfg)
    y, counts = moe.held_moe_block(cfg, x, w)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_dense_reference(cfg, x, w)),
                               rtol=1e-5, atol=1e-5)
    idx, _ = moe.route_sigmoid(cfg, x.reshape(-1, cfg.d_model),
                               w["router"], w["router_bias"])
    local = np.asarray(idx) - cfg.moe.held_start
    held = (local >= 0) & (local < cfg.moe.n_held)
    assert int(counts["held_rows"]) == int(held.sum())
    assert int(counts["tokens_routed"]) == x.shape[0] * x.shape[1]
    assert int(counts["held_rows_max"]) == int(np.bincount(
        local[held], minlength=cfg.moe.n_held).max())


@pytest.mark.parametrize("every_pick_held", [False, True])
def test_dropless_when_every_token_picks_one_held_expert(every_pick_held):
    """A router that sends every token to held expert 5 (and, in the
    second case, every one of its k picks to held experts, which fills
    the row buffer): nothing is dropped, the output is the dense
    reference's, and its gradient too."""
    cfg = CHIP
    m = cfg.moe
    w = _layer_weights(cfg)
    # expert 5 first; then held experts, or experts 0 and 1, held elsewhere
    bias = jnp.zeros((m.n_experts,)).at[5].set(10.0)
    if every_pick_held:
        held = m.held_start + jnp.arange(m.n_held)
        bias = bias.at[held].add(5.0 + jnp.arange(m.n_held) * 0.1)
    else:
        bias = bias.at[jnp.arange(m.top_k - 1)].set(5.0)
    w = dict(w, router=w["router"] * 0.01, router_bias=bias)
    x = _x(cfg)
    t = x.shape[0] * x.shape[1]
    y, counts = moe.held_moe_block(cfg, x, w)
    assert int(counts["held_rows_max"]) == t
    assert int(counts["held_rows"]) == (t * m.top_k if every_pick_held
                                        else t)
    want = _dense_reference(cfg, x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(f, x_, w_):
        return jnp.sum(jnp.sin(f(x_, w_)))

    got = jax.grad(lambda x_, w_: loss(
        lambda a, b: moe.held_moe_block(cfg, a, b)[0], x_, w_),
        argnums=(0, 1))(x, w)
    ref = jax.grad(lambda x_, w_: loss(
        lambda a, b: _dense_reference(cfg, a, b), x_, w_),
        argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_shares_sum_to_the_uncut_layer():
    """Four chips of 4 experts each: their held-expert parts, with the
    shared experts (which every chip computes) counted once, add up to
    the layer holding all 16."""
    w = _layer_weights(FULL)
    x = _x(FULL)
    whole, _ = moe.held_moe_block(FULL, x, w)
    n = 4
    total = -(n - 1) * moe._shared_experts(x, w)
    for s in range(n):
        cfg = dataclasses.replace(FULL, moe=dataclasses.replace(
            FULL.moe, held=n, held_start=n * s))
        part = {k: (v[n * s:n * s + n] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in w.items()}
        total = total + moe.held_moe_block(cfg, x, part)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b",
                                  "moonlight-16b-a3b-chip"])
def test_decode_through_the_cache_matches_the_full_forward(arch):
    """Token by token through the per-head latent-attention cache (the
    leading dense layer's slice first), the logits of every position
    are the full forward's."""
    cfg = get_smoke_config(arch)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    b, l = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, l), 0,
                              cfg.vocab_size)
    full, _ = transformer.forward(cfg, params, toks)
    cache = transformer.init_cache(cfg, b, l)
    assert cache["k"].shape[-1] == cfg.qk_head_dim
    assert cache["v"].shape[-1] == cfg.v_head_dim
    step = jax.jit(lambda p, t, c, i: transformer.forward_decode(
        cfg, p, t, c, i))
    for i in range(l):
        logits, cache = step(params, toks[:, i:i + 1], cache, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, i]),
                                   rtol=1e-4, atol=1e-4)


def _ragged_dot_with_garbage(real, garbage=7.0):
    """``jax.lax.ragged_dot`` whose rows past the groups, in its output
    and in its lhs gradient, hold ``garbage``: on a TPU those rows are
    left unspecified, where the CPU gives zeros."""
    def fake(a, b, group_sizes, **kw):
        past = (jnp.arange(a.shape[0]) >= jnp.sum(group_sizes))[:, None]

        @jax.custom_vjp
        def f(a, b):
            return jnp.where(past, garbage, real(a, b, group_sizes, **kw))

        def f_bwd(res, g):
            _, vjp = jax.vjp(lambda a_, b_: real(a_, b_, group_sizes, **kw),
                             *res)
            da, db = vjp(g)
            return jnp.where(past, garbage, da).astype(da.dtype), db

        f.defvjp(lambda a, b: (f(a, b), (a, b)), f_bwd)
        return f(a, b)
    return fake


def test_rows_past_the_groups_are_never_read(monkeypatch):
    """The row buffer holds t * min(k, held) rows and the routed ones
    fill only its head: the layer's output and gradients must not read
    what the grouped matmuls leave in the rest, forward or backward
    (a plain ``jnp.take``, whose transpose scatter-adds every row of the
    buffer back onto the tokens, does)."""
    cfg = CHIP
    w, x = _layer_weights(cfg), _x(cfg)

    def loss(x_, w_):
        return jnp.sum(jnp.sin(moe.held_moe_block(cfg, x_, w_)[0]))

    want = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _ragged_dot_with_garbage(jax.lax.ragged_dot))
    got = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
