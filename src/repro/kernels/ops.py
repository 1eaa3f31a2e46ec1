"""Jit'd public wrappers around the PS-path Pallas kernels.

These wrappers fall back cleanly on every backend: off TPU the kernels
execute in ``interpret=True`` mode (same code path, CPU semantics), and
``flash_attention`` additionally drops to the jnp reference when the
sequence lengths do not divide its block size — callers never see a
TPU-only error.  On TPU the same calls compile natively.  The
flash-attention wrapper adds a ``jax.custom_vjp`` whose backward is the
registry's (``registry.attention_bwd``): the flash backward kernel where
its blocks tile the shapes, else the jnp reference's vjp.

The worker-step ops (attention / rmsnorm / residual_rmsnorm / ssm_scan)
have moved behind the enum-dispatched ``repro.kernels.registry``; the
wrappers here serve the server/compression path (fused update, wire
codecs) plus direct kernel experimentation.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_compress as _fc
from repro.kernels import fused_update as _fu
from repro.kernels import ref as _ref
from repro.kernels import registry as _registry
from repro.kernels import rmsnorm as _rn


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------------------ flash attn
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    block: int = 128):
    lq, lk = q.shape[1], k.shape[1]
    if lq % min(block, lq) or lk % min(block, lk):
        # block does not tile the sequence: clean reference fallback
        # (same math, same vjp) instead of the kernel's grid error
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window)
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=min(block, lq),
                                   block_k=min(block, lk),
                                   interpret=not on_tpu())


def _fa_fwd(q, k, v, causal, window, block):
    out = flash_attention(q, k, v, causal, window, block)
    return out, (q, k, v, out)


def _fa_bwd(causal, window, block, res, dout):
    return _registry.attention_bwd(*res, dout, causal=causal,
                                   window=window, interpret=not on_tpu())


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ------------------------------------------------------------ rmsnorm
def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    return _rn.rmsnorm(x, weight, eps=eps, interpret=not on_tpu())


# ------------------------------------------------------------ fused update
def fused_update(p: jax.Array, m: jax.Array, g: jax.Array, *,
                 lr, beta: float = 0.9, scale=1.0,
                 ) -> Tuple[jax.Array, jax.Array]:
    return _fu.fused_update(p, m, g, lr=lr, beta=beta, scale=scale,
                            interpret=not on_tpu())


def fused_update_batched(p, m, gs, *, lr, beta: float = 0.9, scales=None):
    """Coalesced apply: K stacked gradient buffers folded through
    momentum in ONE pallas_call, bitwise-identical to K sequential
    ``fused_update`` calls in stack order (see kernels/fused_update)."""
    return _fu.fused_update_batched(p, m, gs, lr=lr, beta=beta,
                                    scales=scales, interpret=not on_tpu())


def fused_update_shard(ps, ms, gs, *, lr, beta: float = 0.9, scale=1.0):
    """Batched shard apply: all leaves through ONE pallas_call (packed
    (rows, 512) layout) — the sharded PS's per-shard update kernel."""
    return _fu.fused_update_shard(ps, ms, gs, lr=lr, beta=beta, scale=scale,
                                  interpret=not on_tpu())


def fused_int8_ef(g, err):
    """Fused int8 quantize+dequant+error-feedback over a packed wire
    buffer — ONE kernel launch per shard (see kernels/fused_compress)."""
    return _fc.fused_int8_ef(g, err, interpret=not on_tpu())


def fused_topk_ef(g, err, *, fraction: float = 0.05):
    """Fused per-tile magnitude top-k + error feedback on the wire."""
    return _fc.fused_topk_ef(g, err, fraction=fraction,
                             interpret=not on_tpu())


def fused_update_tree(params, momenta, grads, *, lr, beta: float = 0.9,
                      scale=1.0):
    """Tree-mapped fused update (the DSSP pipeline's apply phase)."""
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_m = jax.tree_util.tree_leaves(momenta)
    flat_g = jax.tree_util.tree_leaves(grads)
    outs = [fused_update(p, m, g, lr=lr, beta=beta, scale=scale)
            for p, m, g in zip(flat_p, flat_m, flat_g)]
    return (tdef.unflatten([o[0] for o in outs]),
            tdef.unflatten([o[1] for o in outs]))
