"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and finiteness; plus a decode step where the
family supports serving."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import arch_names, get_smoke_config
from repro.models import registry

ARCHS = arch_names()


def _batch(cfg, b=2, l=16, seed=0):
    k = jax.random.PRNGKey(seed)
    toks = jax.random.randint(k, (b, l), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(k, (b, l, cfg.d_model),
                                            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss_fn = registry.loss_fn(cfg)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, b)
        return loss, grads

    loss, grads = step(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    finite = jax.tree_util.tree_map(
        lambda g: bool(jnp.isfinite(g).all()), grads)
    assert jax.tree_util.tree_all(finite), f"{arch}: non-finite grads"
    # one SGD step actually changes the params
    new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    changed = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), params, new)
    assert any(jax.tree_util.tree_leaves(changed)), f"{arch}: params frozen"


@pytest.mark.parametrize("arch", ARCHS)
def test_loss_decreases_smoke(arch):
    """A few SGD steps on one repeated batch must reduce the loss."""
    cfg = get_smoke_config(arch)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss_fn = registry.loss_fn(cfg)

    @jax.jit
    def step(p, b):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, jax.tree_util.tree_map(lambda x, g: x - 0.05 * g, p,
                                            grads)

    first, params = step(params, batch)
    last = first
    for _ in range(5):
        last, params = step(params, batch)
    assert float(last) < float(first), f"{arch}: {first} -> {last}"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(arch):
    cfg = get_smoke_config(arch)
    fam = registry.family(cfg)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    b, max_seq = 2, 16
    if cfg.family == "audio":
        state = fam.init_state(cfg, b, max_seq, max_seq)
    else:
        state = fam.init_state(cfg, b, max_seq)
    token = jnp.zeros((b, 1), jnp.int32)

    @jax.jit
    def step(p, t, s, i):
        return fam.decode_fn(cfg, p, t, s, i)

    logits, state = step(params, token, state, jnp.int32(0))
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite decode"
    # second step consumes the returned state
    logits2, _ = step(params, token, state, jnp.int32(1))
    assert bool(jnp.isfinite(logits2).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count(arch):
    """Full configs instantiate defs only (no arrays) and land in the
    right parameter-count ballpark for their published size."""
    from repro.configs import get_config
    cfg = get_config(arch)
    n = registry.count_params(cfg)
    expected = {
        "h2o-danube-1.8b": (1.4e9, 2.3e9),
        "h2o-danube-1.8b-chip": (0.65e9, 0.8e9),
        "qwen1.5-110b": (95e9, 125e9),
        "qwen1.5-32b": (28e9, 38e9),
        "mistral-large-123b": (110e9, 135e9),
        "qwen3-moe-235b-a22b": (200e9, 270e9),
        "deepseek-moe-16b": (14e9, 20e9),
        "xlstm-125m": (0.08e9, 0.2e9),
        "whisper-tiny": (0.02e9, 0.08e9),
        "chameleon-34b": (30e9, 39e9),
        "jamba-v0.1-52b": (45e9, 60e9),
        "moonlight-16b-a3b": (15.9e9, 16.0e9),
        "moonlight-16b-a3b-chip": (0.568e9, 0.569e9),
    }[arch]
    assert expected[0] <= n <= expected[1], f"{arch}: {n/1e9:.2f}B params"


# --------------------------------------------- model.kernels dispatch e2e
# The registry knob must be a pure numerics-preserving dispatch: the same
# smoke config trained under any valid ``model.kernels`` string yields a
# finite loss that matches the "auto" run to float tolerance (Pallas
# variants run interpret=True on CPU).
_KERNEL_ARCHS = ["h2o-danube-1.8b",   # pure attention stack
                 "xlstm-125m",        # recurrent family
                 "jamba-v0.1-52b"]    # hybrid: attention + mamba scan


def _one_step_loss(cfg, seed=0):
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, seed=seed)
    loss_fn = registry.loss_fn(cfg)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, batch)
    finite = jax.tree_util.tree_map(
        lambda g: bool(jnp.isfinite(g).all()), grads)
    assert jax.tree_util.tree_all(finite), f"{cfg.name}: non-finite grads"
    return float(loss)


@pytest.mark.parametrize("arch", _KERNEL_ARCHS)
@pytest.mark.parametrize("kernels", [
    "pallas",
    "xla",
    "attention=xla,ssm_scan=xla_associative",
])
def test_model_kernels_knob_smoke(arch, kernels):
    import dataclasses
    cfg = get_smoke_config(arch)
    base = _one_step_loss(cfg)                      # kernels == "auto"
    got = _one_step_loss(dataclasses.replace(cfg, kernels=kernels))
    assert jnp.isfinite(got)
    assert abs(got - base) <= 1e-3 * max(1.0, abs(base)), \
        f"{arch} kernels={kernels}: loss {got} vs auto {base}"
