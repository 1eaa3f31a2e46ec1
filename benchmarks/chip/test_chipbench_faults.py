"""A run with the timed path broken underneath comes out not correct,
once for each fault a training cell can have on one chip: a step that
leaves its state unchanged, and half of the batch left out with the
mean taken over the rest.  (There is no exchange between chips in a
one-chip cell, and a training step produces no token or answer to
alter.)"""

import dataclasses

import jax.numpy as jnp
import pytest

from chip.conftest import run_smoke


def _state_unchanged_ps(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "fused_update",
                        lambda p, m, g, **kw: (p, m))


def _state_unchanged_spmd(monkeypatch):
    from repro.launch import train
    real = train.make_optimizer

    def frozen(*a, **kw):
        opt = real(*a, **kw)
        return dataclasses.replace(
            opt, update=lambda g, s, p, **kw: (p, s))

    monkeypatch.setattr(train, "make_optimizer", frozen)


def _half_batch(monkeypatch):
    from repro.models import registry
    real = registry.loss_fn

    def half(cfg):
        fn = real(cfg)

        def loss(params, batch):
            labels = batch["labels"]
            keep = jnp.arange(labels.size).reshape(labels.shape) \
                < labels.size // 2
            return fn(params, dict(batch, labels=jnp.where(keep, labels,
                                                           -1)))
        return loss

    monkeypatch.setattr(registry, "loss_fn", half)


@pytest.mark.parametrize("workload,fault", [
    ("danube-ps.solo", _state_unchanged_ps),
    ("danube-ps.solo", _half_batch),
    ("danube-spmd.adamw", _state_unchanged_spmd),
    ("danube-spmd.adamw", _half_batch),
])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = run_smoke(workload)
    assert not out["correct"], out["checks"]
