"""The worker's step on the packed wire.

``packed_step(plan, tree_step)`` turns a pytree step
``(params, batch) -> (grads, aux)`` into the one jitted program a
parameter-server worker runs:

    _packed_step(wire_p, wire_g_prev, batch) -> (wire_g, aux)

It unpacks the pulled (rows, 512) parameter wire, runs the step, and
packs the gradients into ``wire_g_prev``'s donated memory, so the
pytree <-> wire boundary is crossed once per direction, inside the jit.
The unpack and the pack sit in ``phase("wire")`` scopes, the device
phase the trace folds them into.  ``PSWorker`` owns the gradient buffer
it donates back every iteration; the parameter wire is the server's
shared snapshot and is never donated.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from repro.obs.device import phase

TreeStep = Callable[[Any, Any], Any]  # (params, batch) -> (grads, aux)


def packed_step(plan, tree_step: TreeStep):
    """The jitted packed step around ``tree_step`` for ``plan``'s wire
    layout."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _packed_step(wire_p, wire_g_prev, batch):
        with phase("wire"):
            p = plan.unpack(wire_p)
        grads, aux = tree_step(p, batch)
        # Write the packed grads INTO the donated buffer: the output
        # aliases wire_g_prev's memory.  A plain `return plan.pack(...)`
        # would leave wire_g_prev unread, and jit's keep_unused=False
        # prunes unread args before donation.
        with phase("wire"):
            wire_g = wire_g_prev.at[:].set(plan.pack(grads))
        return wire_g, aux

    return _packed_step


def loss_step(loss_fn) -> TreeStep:
    """The pytree step of a model loss ``(params, batch) -> (loss,
    metrics)``: the gradient, and ``aux`` with the loss and the model's
    perfcount groups (``metrics["counts"]``) when it reports them."""

    def tree_step(p, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, batch)
        aux = {"loss": loss}
        if "counts" in metrics:  # the model's perfcount groups
            aux["counts"] = metrics["counts"]
        return grads, aux

    return tree_step
