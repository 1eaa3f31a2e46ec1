"""The float32 reference against the program at smoke size on the CPU,
so that it is shown right before it judges the chip: each model
family's loss and gradient (``registry.loss_fn`` with its XLA kernels)
and weights, the server's apply (``kops.fused_update``) and the
trainer's AdamW."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import cells
from chip import reference as ref
from chip.conftest import FAMILIES


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    """(family module, its smoke sizes, the program's smoke config)."""
    from repro.configs import get_smoke_config
    fam = cells.load_family(request.param)
    return (fam, fam.sizes(fam.SMOKE["config"]),
            get_smoke_config(fam.SMOKE["arch"]))


def test_weights_are_the_programs(family):
    from repro.models import registry
    fam, m, program_cfg = family
    key = jax.random.PRNGKey(3)
    mine = fam.init_params(m, key, jnp.float32, eager=True)
    theirs = registry.init_params(program_cfg, key)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loss_and_gradient_match_the_program(family):
    from repro.models import registry
    fam, m, program_cfg = family
    params = fam.init_params(m, jax.random.PRNGKey(0), jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    # longer than a smoke window (16): a sliding mask is exercised
    vocab = m["vocab_size"]
    toks = jax.random.randint(k1, (2, 40), 0, vocab)
    labs = jax.random.randint(k2, (2, 40), 0, vocab)
    lfn = registry.loss_fn(dataclasses.replace(program_cfg, kernels="xla"))
    with jax.default_matmul_precision("highest"):
        (want, _), g_want = jax.value_and_grad(lfn, has_aux=True)(
            params, {"tokens": toks, "labels": labs})
    got, g_got = fam.loss_and_grad(m, params, toks, labs)
    assert got == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale
    # the control rounds every matmul operand to float8: visibly off
    low, _ = fam.loss_and_grad(m, params, toks, labs, prec="fp8",
                               with_grad=False)
    assert abs(low - got) > 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_momentum_apply_matches_fused_update(dtype):
    from repro.kernels import ops as kops
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    p, m = (jax.random.normal(x, (64, 512)).astype(dtype) for x in k[:2])
    g = jax.random.normal(k[2], (64, 512)).astype(dtype)
    want_p, want_m = kops.fused_update(p, m, g, lr=0.1, beta=0.9,
                                       scale=0.5)
    got_p, got_m = ref.momentum_apply(
        jnp.copy(p), jnp.copy(m), g.astype(jnp.float32), lr=0.1,
        beta=0.9, scale=0.5, store=jnp.dtype(dtype))
    eps = float(jnp.finfo(dtype).eps)
    for a, b in ((got_p, want_p), (got_m, want_m)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b))) <= \
            eps * float(jnp.max(jnp.abs(b)))


def test_adamw_apply_matches_the_trainers():
    from repro.optim import make_optimizer
    opt = make_optimizer("adamw", 3e-3, staleness_damping=True)
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    p = {"w": jax.random.normal(k[0], (32, 16)).astype(jnp.bfloat16)}
    state = opt.init(p)
    got_p, mu, nu = p["w"], jnp.zeros((32, 16)), jnp.zeros((32, 16))
    for step, key in enumerate(k[1:]):
        g = {"w": jax.random.normal(key, (32, 16))}
        p, state = opt.update(g, state, p, staleness=1,
                              lr_scale=float(step > 0))
        got_p, mu, nu = ref.adamw_apply(
            got_p, mu, nu, g["w"], jnp.int32(step + 1), jnp.float32(0.5),
            jnp.float32(float(step > 0)), lr=3e-3, b1=0.9, b2=0.95,
            eps=1e-8, weight_decay=0.0)
        # the two compilers may contract the multiply-adds differently
        want_mu = np.asarray(state.mu["w"])
        np.testing.assert_allclose(np.asarray(mu), want_mu, rtol=1e-6,
                                   atol=1e-6 * np.abs(want_mu).max())
        a = np.asarray(got_p.astype(jnp.float32))
        b = np.asarray(p["w"].astype(jnp.float32))
        assert np.abs(a - b).max() <= 2**-8 * np.abs(b).max()


def test_worst_leaf_gap_is_relative_to_leaf_or_median():
    want = np.array([1.0, 2.0, 3.0, 0.0])
    # the empty leaf is measured against the median (1.5), not against 0
    assert cells.worst_leaf_gap(np.array([1.0, 2.0, 3.0, 0.5]),
                                want) == pytest.approx(0.5 / 1.5)
    assert cells.worst_leaf_gap(np.array([1.0, 2.2, 3.0, 0.0]),
                                want) == pytest.approx(0.1)
    keep = np.array([True, False, True, True])
    assert cells.worst_leaf_gap(np.array([1.0, 9.0, 3.0, 0.0]), want,
                                keep) == 0.0
