"""What every family's plain reference shares: float32 ``jax.numpy``
pieces at ``HIGHEST`` precision, the weights made from a family's
layout, and the momentum-SGD and AdamW updates the two trainers apply.
It imports nothing of the program.  Each model family's forward, loss,
gradient and weight tree is its own module under ``families/``.

Matmuls run at ``HIGHEST`` precision (on a TPU a float32 matmul is
otherwise done in bfloat16).  ``prec='fp8'`` rounds every matmul
operand to float8 e4m3: that is the control, the reference one
precision step below the bfloat16 the configurations state, which the
comparison has to reject: each operand, and the gradient flowing back
into it, is scaled per tensor so that its largest magnitude is float8's
largest, as float8 training does.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


# ------------------------------------------------------------ weights
def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _make(layout_of: Callable[[Dict], Any], m: Dict, key: jax.Array,
          dtype):
    layout = layout_of(m)
    specs = jax.tree_util.tree_leaves(layout, is_leaf=_is_spec)
    keys = iter(jax.random.split(key, len(specs)))

    def make(spec):
        shape, init, fan_in = spec
        k = next(keys)
        if init == "ones":
            return jnp.ones(shape, dtype)
        return (jax.random.normal(k, shape, jnp.float32)
                * (1.0 / math.sqrt(max(1, fan_in)))).astype(dtype)

    return jax.tree_util.tree_map(make, layout, is_leaf=_is_spec)


def init_params(layout_of: Callable[[Dict], Any], m: Dict, key: jax.Array,
                dtype=jnp.bfloat16, *, eager: bool = False):
    """The weights of the tree ``layout_of(m)`` gives as ``{name: (shape,
    init, fan_in)}`` (init 'normal' draws N(0, 1/fan_in), 'ones' fills
    1): one key per leaf, split from ``key`` in leaf order.  Made on the
    device in one jitted call; ``eager`` makes them op by op instead, as
    the SPMD trainer makes its own (jitted, the normal draw may round
    differently in the last bit)."""
    if eager:
        return _make(layout_of, m, key, jnp.dtype(dtype))
    return _init_jit(layout_of, hashable(m), jnp.dtype(dtype))(key)


@functools.lru_cache(maxsize=None)
def _init_jit(layout_of: Callable, mkey: Tuple, dtype):
    return jax.jit(functools.partial(_make, layout_of, dict(mkey),
                                     dtype=dtype))


def hashable(m: Dict) -> Tuple:
    """The scalar sizes of ``m``, as a key for a cache of compiled
    pieces."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


# ------------------------------------------------------------ pieces
def _round_fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale that maps its
    largest magnitude to the format's largest (448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


# the gradient flowing back into an operand is rounded the same way
_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


def cast(x, prec: str):
    """x in float32, rounded through float8 under ``prec='fp8'``."""
    x = x.astype(jnp.float32)
    return _fp8(x) if prec == "fp8" else x


def mm(eq: str, a, b, prec: str):
    """``einsum(eq, a, b)`` in float32 at ``HIGHEST`` on operands cast by
    ``prec``."""
    return jnp.einsum(eq, cast(a, prec), cast(b, prec), precision=HIGHEST)


def rms(x, w, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, seq: int, theta: float):
    """Rotary position embedding of x (b, l, h, d) over its last axis,
    the two halves rotated together."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_mask(seq: int, window):
    """(seq, seq) True where a query sees a key: causal, and within
    ``window`` keys when one is given."""
    q, k = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    mask = k <= q
    if window:
        mask &= k > q - window
    return mask


# ------------------------------------------------------------ updates
@functools.partial(jax.jit, static_argnames=("lr", "beta", "scale",
                                             "store"),
                   donate_argnums=(0, 1))
def momentum_apply(p, m, g, *, lr: float, beta: float, scale: float,
                   store):
    """The server's update on one leaf: m = beta*m + scale*g, p -= lr*m,
    in float32, each stored back in ``store`` (the dtype the
    configuration keeps the server state in)."""
    m_new = beta * m.astype(jnp.float32) + scale * g
    p_new = p.astype(jnp.float32) - lr * m_new
    return p_new.astype(store), m_new.astype(store)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "weight_decay"),
                   donate_argnums=(0, 1, 2))
def adamw_apply(p, mu, nu, g, count, scale, lr_scale, *, lr: float,
                b1: float, b2: float, eps: float, weight_decay: float):
    """AdamW on one leaf: moments in float32, the damped gradient
    ``scale * g`` into the first moment, the raw one into the second,
    bias correction by ``count``; p stored back in its dtype."""
    mu = b1 * mu + (1 - b1) * g * scale
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    upd = (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    pf = pf - lr * lr_scale * (upd + weight_decay * pf)
    return pf.astype(p.dtype), mu, nu
