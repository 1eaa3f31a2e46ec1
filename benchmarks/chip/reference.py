"""The plain reference: h2o-danube (a Mistral-style decoder) forward,
loss and gradient in float32 ``jax.numpy``, with the momentum-SGD and
AdamW updates the two trainers apply.  It imports nothing of the
program and reads its sizes from the configuration file.

It runs layer by layer so that it fits one chip beside nothing else:
the forward keeps each layer's input, the backward takes one layer's
vector-Jacobian product at a time.  Matmuls run at ``HIGHEST``
precision (on a TPU a float32 matmul is otherwise done in bfloat16).
``prec='fp8'`` rounds every matmul operand to float8 e4m3: that is the
control, the reference one precision step below the bfloat16 the
configuration states, which the comparison has to reject: each operand,
and the gradient flowing back into it, is scaled per tensor so that its
largest magnitude is float8's largest, as float8 training does.

Weights follow the program's parameter tree (the checkpoint layout its
``params`` override takes): ``embed``, ``final_norm.scale``,
``layers.{attn.{wq,wk,wv,wo}, attn_norm.scale, mlp.{w_gate,w_up,
w_down}, mlp_norm.scale}`` stacked over layers, ``unembed``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
VOCAB_PAD = 16     # the program pads embedding rows to a multiple of this


# ------------------------------------------------------------ weights
def padded_vocab(m: Dict) -> int:
    return -(-m["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def param_layout(m: Dict) -> Dict[str, Any]:
    """{name: (shape, init, fan_in)}: init 'normal' draws N(0, 1/fan_in),
    'ones' fills 1."""
    n, d, f = m["num_hidden_layers"], m["hidden_size"], \
        m["intermediate_size"]
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    v = padded_vocab(m)
    return {
        "embed": ((v, d), "normal", d),
        "final_norm": {"scale": ((d,), "ones", 0)},
        "layers": {
            "attn": {"wq": ((n, d, hq, hd), "normal", d),
                     "wk": ((n, d, hkv, hd), "normal", d),
                     "wv": ((n, d, hkv, hd), "normal", d),
                     "wo": ((n, hq, hd, d), "normal", hq * hd)},
            "attn_norm": {"scale": ((n, d), "ones", 0)},
            "mlp": {"w_gate": ((n, d, f), "normal", d),
                    "w_up": ((n, d, f), "normal", d),
                    "w_down": ((n, f, d), "normal", f)},
            "mlp_norm": {"scale": ((n, d), "ones", 0)},
        },
        "unembed": ((v, d), "normal", d),
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _make(m: Dict, key: jax.Array, dtype):
    layout = param_layout(m)
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


def init_params(m: Dict, key: jax.Array, dtype=jnp.bfloat16, *,
                eager: bool = False):
    """The weights: one key per leaf, split from ``key`` in leaf order.
    Made on the device in one jitted call; ``eager`` makes them op by op
    instead, as the SPMD trainer makes its own (jitted, the normal draw
    may round differently in the last bit)."""
    if eager:
        return _make(m, key, jnp.dtype(dtype))
    return _init_jit(_hashable(m), jnp.dtype(dtype))(key)


@functools.lru_cache(maxsize=None)
def _init_jit(mkey: Tuple, dtype):
    return jax.jit(functools.partial(_make, dict(mkey), dtype=dtype))


# ------------------------------------------------------------ forward
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


def _cast(x, prec: str):
    x = x.astype(jnp.float32)
    return _fp8(x) if prec == "fp8" else x


def _mm(eq: str, a, b, prec: str):
    return jnp.einsum(eq, _cast(a, prec), _cast(b, prec), precision=HIGHEST)


def _rms(x, w, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, seq: int, theta: float):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mask(seq: int, window):
    q, k = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    mask = k <= q
    if window:
        mask &= k > q - window
    return mask


def layer(m: Dict, prec: str, x, w):
    """One pre-norm block on x (b, l, d) float32."""
    eps, l = m["rms_norm_eps"], x.shape[1]
    g = m["num_attention_heads"] // m["num_key_value_heads"]
    a = w["attn"]
    h = _rms(x, w["attn_norm"]["scale"], eps)
    q = _rope(_mm("bld,dhk->blhk", h, a["wq"], prec), l, m["rope_theta"])
    k = _rope(_mm("bld,dhk->blhk", h, a["wk"], prec), l, m["rope_theta"])
    v = _mm("bld,dhk->blhk", h, a["wv"], prec)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = _mm("blhd,bmhd->bhlm", q, k, prec) / math.sqrt(q.shape[-1])
    s = jnp.where(_mask(l, m.get("sliding_window"))[None, None], s, NEG_INF)
    o = _mm("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v, prec)
    x = x + _mm("blhk,hkd->bld", o, a["wo"], prec)
    h = _rms(x, w["mlp_norm"]["scale"], eps)
    u = w["mlp"]
    act = jax.nn.silu(_mm("bld,df->blf", h, u["w_gate"], prec)) \
        * _mm("bld,df->blf", h, u["w_up"], prec)
    return x + _mm("blf,fd->bld", act, u["w_down"], prec)


def head_loss_sum(m: Dict, prec: str, x, final_scale, unembed, labels):
    """Summed next-token NLL of x (b, l, d) through the final norm and
    the unembedding (padded vocabulary rows masked out); a label of -1
    is left out."""
    h = _rms(x, final_scale, m["rms_norm_eps"])
    logits = _mm("bld,vd->blv", h, unembed, prec)
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < m["vocab_size"], logits, NEG_INF)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(labels >= 0, logz - gold, 0.0))


def _hashable(m: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


@functools.lru_cache(maxsize=None)
def _fns(mkey: Tuple, prec: str):
    """Jitted pieces for one configuration and precision (the layer
    index is traced, so one compile serves every layer)."""
    m = dict(mkey)

    def layer_w(params, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False).astype(jnp.float32),
            params["layers"])

    @jax.jit
    def embed(params, toks):
        return jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)

    @jax.jit
    def fwd(params, i, x):
        return layer(m, prec, x, layer_w(params, i))

    @jax.jit
    def bwd(params, i, x, dy):
        _, vjp = jax.vjp(lambda x_, w_: layer(m, prec, x_, w_), x,
                         layer_w(params, i))
        return vjp(dy)

    @jax.jit
    def head(params, x, labels, norm):
        f = functools.partial(head_loss_sum, m, prec)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
            x, params["final_norm"]["scale"].astype(jnp.float32),
            params["unembed"].astype(jnp.float32), labels)
        return loss / norm, jax.tree_util.tree_map(lambda g: g / norm,
                                                   grads)

    @jax.jit
    def head_fwd(params, x, labels, norm):
        return head_loss_sum(
            m, prec, x, params["final_norm"]["scale"].astype(jnp.float32),
            params["unembed"].astype(jnp.float32), labels) / norm

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_layer(acc, i, dw):
        return jax.tree_util.tree_map(
            lambda a, d: a.at[i].add(d), acc, dw)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_embed(acc, toks, dx):
        return acc.at[toks.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))

    return dict(embed=embed, fwd=fwd, bwd=bwd, head=head,
                head_fwd=head_fwd, add_layer=add_layer, add_embed=add_embed)


def _zeros_f32(params):
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)


def loss_and_grad(m: Dict, params, tokens, labels, prec: str = "f32",
                  with_grad: bool = True, half: bool = False):
    """Mean next-token loss over every token of the batch and, with
    ``with_grad``, its float32 gradient as a tree like ``params``.
    Row by row and layer by layer, so one layer's scores of one row is
    the largest thing alive beside the weights and the gradient.
    ``half`` leaves out the second half of the batch's tokens and takes
    the mean over the rest: a fault the comparison has to reject."""
    fn = _fns(_hashable(m), prec)
    n = m["num_hidden_layers"]
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    if half:
        kept = jnp.arange(labels.size).reshape(labels.shape) \
            < labels.size // 2
        labels = jnp.where(kept, labels, -1)
    norm = jnp.float32(jnp.sum(labels >= 0))
    total = 0.0
    grads = _zeros_f32(params) if with_grad else None
    for r in range(tokens.shape[0]):
        toks, labs = tokens[r:r + 1], labels[r:r + 1]
        xs = [fn["embed"](params, toks)]
        for i in range(n):
            xs.append(fn["fwd"](params, jnp.int32(i), xs[-1]))
        if not with_grad:
            total += float(fn["head_fwd"](params, xs[-1], labs, norm))
            continue
        loss, (dx, dfin, dun) = fn["head"](params, xs[-1], labs, norm)
        total += float(loss)
        grads["final_norm"]["scale"] = grads["final_norm"]["scale"] + dfin
        grads["unembed"] = grads["unembed"] + dun
        xs.pop()
        for i in reversed(range(n)):
            dx, dw = fn["bwd"](params, jnp.int32(i), xs.pop(), dx)
            grads["layers"] = fn["add_layer"](grads["layers"],
                                              jnp.int32(i), dw)
        grads["embed"] = fn["add_embed"](grads["embed"], toks, dx)
    return total, grads


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
