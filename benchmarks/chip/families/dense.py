"""The dense family: a Mistral-style decoder (h2o-danube) of pre-norm
blocks, grouped-query attention with rotary embeddings and an optional
sliding window, and a SwiGLU MLP.  A configuration file that names no
``family`` is of this one.

Its plain reference takes the loss and gradient in float32 layer by
layer, so that it fits one chip beside nothing else: the forward keeps
each layer's input, the backward takes one layer's vector-Jacobian
product at a time.  Weights follow the program's parameter tree (the
checkpoint layout its ``params`` override takes): ``embed``,
``final_norm.scale``, ``layers.{attn.{wq,wk,wv,wo}, attn_norm.scale,
mlp.{w_gate,w_up,w_down}, mlp_norm.scale}`` stacked over layers,
``unembed``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chip import peaks
from chip import reference as ref

#: The configuration file's keys this family reads.
KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers",
        "vocab_size", "sliding_window", "rope_theta", "rms_norm_eps")
VOCAB_PAD = 16     # the program pads embedding rows to a multiple of this

#: The registry arch whose smoke configuration ``config`` is, for the CPU
#: tests, with counts worked out by hand at those sizes.  Per layer: q
#: and o 64*4*16 = 4096 each, k and v 64*2*16 = 2048 each, the MLP
#: 3*64*160 = 30720; the 2 layers and the unembedding 256*64 take part in
#: matmuls (102400), beside the embedding and 2*2 + 1 norms of 64.  At
#: seq 32 under the window of 16 a head attends 16*17/2 + 16*16 = 392
#: pairs, 2 FLOPs for each of QK and PV per pair and head_dim element.
SMOKE = {
    "arch": "h2o-danube-1.8b-chip",
    "config": dict(hidden_size=64, intermediate_size=160,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, num_hidden_layers=2, vocab_size=256,
                   sliding_window=16, rope_theta=10000.0,
                   rms_norm_eps=1e-6, torch_dtype="float32"),
    "param_count": 102400 + 256 * 64 + 5 * 64,
    "train_flops_per_token": {
        32: 3.0 * (2 * 102400 + 2 * 4 * 4 * 16 * 392 / 32)},
}


def sizes(cfg: Dict) -> Dict[str, Any]:
    """The configuration file's sizes."""
    return {k: cfg[k] for k in KEYS}


def program_sizes(c) -> Dict[str, Any]:
    """The same sizes as the registry's ``ModelConfig`` states them; the
    program's RMSNorm takes a fixed epsilon and states none."""
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads,
            "head_dim": c.resolved_head_dim,
            "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
            "sliding_window": c.sliding_window,
            "rope_theta": c.rope_theta}


# ------------------------------------------------------------ weights
def padded_vocab(m: Dict) -> int:
    return -(-m["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def param_layout(m: Dict) -> Dict[str, Any]:
    """{name: (shape, init, fan_in)}, as ``reference.init_params`` takes
    it."""
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


def init_params(m: Dict, key: jax.Array, dtype=jnp.bfloat16, *,
                eager: bool = False):
    """The weights in the program's tree (``reference.init_params``)."""
    return ref.init_params(param_layout, m, key, dtype, eager=eager)


# ------------------------------------------------------------ forward
def layer(m: Dict, prec: str, x, w):
    """One pre-norm block on x (b, l, d) float32."""
    eps, l = m["rms_norm_eps"], x.shape[1]
    g = m["num_attention_heads"] // m["num_key_value_heads"]
    a, mm = w["attn"], ref.mm
    h = ref.rms(x, w["attn_norm"]["scale"], eps)
    q = ref.rope(mm("bld,dhk->blhk", h, a["wq"], prec), l, m["rope_theta"])
    k = ref.rope(mm("bld,dhk->blhk", h, a["wk"], prec), l, m["rope_theta"])
    v = mm("bld,dhk->blhk", h, a["wv"], prec)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = mm("blhd,bmhd->bhlm", q, k, prec) / math.sqrt(q.shape[-1])
    s = jnp.where(ref.causal_mask(l, m.get("sliding_window"))[None, None],
                  s, ref.NEG_INF)
    o = mm("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v, prec)
    x = x + mm("blhk,hkd->bld", o, a["wo"], prec)
    h = ref.rms(x, w["mlp_norm"]["scale"], eps)
    u = w["mlp"]
    act = jax.nn.silu(mm("bld,df->blf", h, u["w_gate"], prec)) \
        * mm("bld,df->blf", h, u["w_up"], prec)
    return x + mm("blf,fd->bld", act, u["w_down"], prec)


def head_loss_sum(m: Dict, prec: str, x, final_scale, unembed, labels):
    """Summed next-token NLL of x (b, l, d) through the final norm and
    the unembedding (padded vocabulary rows masked out); a label of -1
    is left out."""
    h = ref.rms(x, final_scale, m["rms_norm_eps"])
    logits = ref.mm("bld,vd->blv", h, unembed, prec)
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < m["vocab_size"], logits, ref.NEG_INF)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(labels >= 0, logz - gold, 0.0))


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
    ``prec='fp8'`` is the control (``reference.cast``).  ``half`` leaves
    out the second half of the batch's tokens and takes the mean over
    the rest: a fault the comparison has to reject."""
    fn = _fns(ref.hashable(m), prec)
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


# ------------------------------------------------------------ counts
def matmul_params(m: Dict) -> int:
    """Parameters that take part in a matmul per token: attention
    projections, the SwiGLU MLP and the unembedding (the embedding is a
    gather)."""
    d, hq, hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd, f = m["head_dim"], m["intermediate_size"]
    per_layer = d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * f
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d


def param_count(m: Dict) -> int:
    """Every parameter: the matmul weights, the embedding, the norms."""
    d, n = m["hidden_size"], m["num_hidden_layers"]
    return matmul_params(m) + m["vocab_size"] * d + (2 * n + 1) * d


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x forward),
    nothing recomputed counted, causal attention counted by the pairs it
    attends."""
    attn_fwd = 4.0 * m["num_attention_heads"] * m["head_dim"] * \
        peaks.attended_pairs(seq, m.get("sliding_window")) / seq
    return 3.0 * (2.0 * matmul_params(m)
                  + m["num_hidden_layers"] * attn_fwd)
