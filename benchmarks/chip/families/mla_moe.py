"""The latent-attention expert family: a DeepSeek-V3 decoder
(Moonlight-16B-A3B) of pre-norm blocks with multi-head latent attention,
``first_k_dense_replace`` leading dense SwiGLU layers, then expert
layers: a sigmoid router over every routed expert whose top-k are picked
on the scores plus a selection-only bias and weighed by the unbiased
scores (renormalised, times ``routed_scaling_factor``), the experts this
chip holds, and the shared experts.

The configuration file counts the experts held here in
``n_routed_experts`` (the chip's share, listed in ``reduced``); the
router scores ``router_outputs`` experts, and those held are
``held_start`` onwards.  A layer's output is its held experts' part plus
the shared experts: the absent experts' part lies on other chips.

Its plain reference takes the loss and gradient in float32 layer by
layer.  Attention runs in blocks of queries, each recomputed in the
backward, so no (seq x seq) score matrix of a head is held whole; the
held experts run densely over every token, each weighed by the router's
weight for the tokens routed to it (zero elsewhere).  The rope rotates
the two halves of the rope part together, as the program does.  Weights
follow the program's tree: ``embed``, ``final_norm.scale``,
``dense_layers`` and ``layers``, each ``{attn.{wq, wkv_a, kv_norm, wkv_b,
wo}, attn_norm.scale, mlp_norm.scale}`` with ``mlp.{w_gate, w_up,
w_down}`` (dense) or ``moe.{router, router_bias, w_gate, w_up, w_down,
shared_gate, shared_up, shared_down}`` (experts) stacked over layers,
``unembed``.
"""

from __future__ import annotations

import functools
import math
from pathlib import Path
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chip import cells, feed, peaks
from chip import reference as ref

_dense = cells.load_family("dense", Path(__file__).resolve().parents[1])

#: The configuration file's keys this family reads.
KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "router_outputs", "held_start", "num_experts_per_tok",
        "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
        "scoring_func", "topk_method", "vocab_size", "rope_theta",
        "rms_norm_eps", "max_position_embeddings")
#: Longest block of queries the reference's attention takes at once.
Q_BLOCK = 1024
#: Rows, each of the published context and from a Markov stream of its
#: own, on which the router bias of each expert layer is balanced when
#: the weights are made (``balance_router_bias``).
BALANCE_ROWS = 8
#: Rounds of the load-driven bias update that balance it.
BALANCE_ROUNDS = 200

#: The registry arch whose smoke configuration ``config`` is, with
#: counts worked by hand.  Attention a layer: wq 64*4*24 = 6144, wkv_a
#: 64*40 = 2560, wkv_b 32*4*32 = 4096, wo 4*16*64 = 4096 (16896 in
#: matmuls) and kv_norm 32.  The dense layer's MLP 3*64*160 = 30720; an
#: expert layer's router 64*16 = 1024 and bias 16, 4 held experts of
#: 3*64*32 = 6144 each and one shared (6144); 2 norms of 64 a layer; the
#: embedding and unembedding 256*64 each, the final norm 64.  A token's
#: matmuls take 3 * 16896 + 30720 + 2 * (1024 + 6144 + 3 * 4/16 * 6144)
#: + 16384 = 121344 parameters (routed experts at the expected 3 of 16
#: experts, 4 of them here); at seq 32 a head attends 528 pairs, 2
#: FLOPs for each of QK (24 wide) and PV (16 wide) per pair.
SMOKE = {
    "arch": "moonlight-16b-a3b-chip",
    "config": dict(hidden_size=64, intermediate_size=160,
                   moe_intermediate_size=32, num_attention_heads=4,
                   num_key_value_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   num_hidden_layers=3, first_k_dense_replace=1,
                   n_routed_experts=4, router_outputs=16, held_start=4,
                   num_experts_per_tok=3, n_shared_experts=1,
                   routed_scaling_factor=2.446, norm_topk_prob=True,
                   scoring_func="sigmoid", topk_method="noaux_tc",
                   vocab_size=256, rope_theta=50000, rms_norm_eps=1e-5,
                   max_position_embeddings=32, torch_dtype="float32"),
    "param_count": (3 * (16896 + 32 + 128) + 30720
                    + 2 * (1024 + 16 + 4 * 6144 + 6144)
                    + 2 * 256 * 64 + 64),
    "train_flops_per_token": {
        32: 3.0 * (2 * 121344 + 3 * 2 * 4 * (24 + 16) * 528 / 32)},
}


def sizes(cfg: Dict) -> Dict[str, Any]:
    """The configuration file's sizes."""
    return {k: cfg[k] for k in KEYS}


def program_sizes(c) -> Dict[str, Any]:
    """The same sizes as the registry's ``ModelConfig`` states them."""
    m = c.moe
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "moe_intermediate_size": m.d_expert,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads,
            "kv_lora_rank": c.kv_lora_rank,
            "qk_nope_head_dim": c.qk_nope_head_dim,
            "qk_rope_head_dim": c.qk_rope_head_dim,
            "v_head_dim": c.v_head_dim, "num_hidden_layers": c.n_layers,
            "first_k_dense_replace": c.first_k_dense,
            "n_routed_experts": m.n_held, "router_outputs": m.n_experts,
            "held_start": m.held_start, "num_experts_per_tok": m.top_k,
            "n_shared_experts": m.n_shared,
            "routed_scaling_factor": m.routed_scale,
            "norm_topk_prob": m.router == "sigmoid_noaux",
            "scoring_func": {"sigmoid_noaux": "sigmoid"}.get(m.router,
                                                             m.router),
            "topk_method": {"sigmoid_noaux": "noaux_tc"}.get(m.router,
                                                             m.router),
            "vocab_size": c.vocab_size, "rope_theta": c.rope_theta,
            "rms_norm_eps": c.rms_norm_eps}


# ------------------------------------------------------------ weights
def _attn_layout(m: Dict, n: int) -> Dict[str, Any]:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return {"wq": ((n, d, h, nope + rope), "normal", d),
            "wkv_a": ((n, d, r + rope), "normal", d),
            "kv_norm": ((n, r), "ones", 0),
            "wkv_b": ((n, r, h, nope + dv), "normal", r),
            "wo": ((n, h, dv, d), "normal", h * dv)}


def _swiglu_layout(n: int, lead: Tuple[int, ...], d: int, f: int):
    return {"w_gate": ((n,) + lead + (d, f), "normal", d),
            "w_up": ((n,) + lead + (d, f), "normal", d),
            "w_down": ((n,) + lead + (f, d), "normal", f)}


def param_layout(m: Dict) -> Dict[str, Any]:
    """{name: (shape, init, fan_in)}, as ``reference.init_params`` takes
    it."""
    d, v = m["hidden_size"], _dense.padded_vocab(m)
    nd = m["first_k_dense_replace"]
    ne = m["num_hidden_layers"] - nd
    e, f = m["n_routed_experts"], m["moe_intermediate_size"]
    fs = f * m["n_shared_experts"]
    norms = {"attn_norm": {"scale": ((ne, d), "ones", 0)},
             "mlp_norm": {"scale": ((ne, d), "ones", 0)}}
    moe = dict(_swiglu_layout(ne, (e,), d, f),
               router=((ne, d, m["router_outputs"]), "normal", d),
               router_bias=((ne, m["router_outputs"]), "normal",
                            m["router_outputs"]))
    moe.update({"shared_" + k[2:]: spec for k, spec in
                _swiglu_layout(ne, (), d, fs).items()})
    out = {"embed": ((v, d), "normal", d),
           "final_norm": {"scale": ((d,), "ones", 0)},
           "layers": dict(norms, attn=_attn_layout(m, ne), moe=moe),
           "unembed": ((v, d), "normal", d)}
    if nd:
        out["dense_layers"] = {
            "attn": _attn_layout(m, nd),
            "attn_norm": {"scale": ((nd, d), "ones", 0)},
            "mlp_norm": {"scale": ((nd, d), "ones", 0)},
            "mlp": _swiglu_layout(nd, (), d, m["intermediate_size"])}
    return out


def init_params(m: Dict, key: jax.Array, dtype=jnp.bfloat16, *,
                eager: bool = False):
    """The weights in the program's tree (``reference.init_params``),
    each expert layer's router bias then balanced on the stream
    (``balance_router_bias``).  ``eager`` gives the program's own
    weights, op by op and unbalanced: a trainer that makes its weights
    itself makes those."""
    params = ref.init_params(param_layout, m, key, dtype, eager=eager)
    if eager:
        return params
    return balance_router_bias(m, params, key)


def balance_router_bias(m: Dict, params, key: jax.Array):
    """``params`` with the router bias of every expert layer balanced:
    on ``BALANCE_ROWS`` rows of ``max_position_embeddings`` tokens, each
    from a Markov stream seeded from ``key``, every routed expert gets
    an equal share of the picks.  This stands in for DeepSeek-V3's
    load-driven update of the bias, which keeps a trained model's
    experts evenly loaded: at random weights attention averages over a
    row, so without it a few experts take most of the tokens, and how
    many land on the held ones changes from seed to seed.  Layer by
    layer in float32: each layer's bias is set before its output feeds
    the next."""
    fn = _fns(ref.hashable(m), "f32")
    xs = [fn["embed"](params, r) for r in balance_rows(m, key)]
    for s in STACKS:
        if s not in params:
            continue
        for i in range(jax.tree_util.tree_leaves(params[s])[0].shape[0]):
            if s == "layers":
                scores = jnp.concatenate([fn["scores"](params, jnp.int32(i),
                                                       x) for x in xs])
                bias = _balanced_bias(scores, m["num_experts_per_tok"])
                moe = params[s]["moe"]
                moe["router_bias"] = moe["router_bias"].at[i].set(
                    bias.astype(moe["router_bias"].dtype))
            xs = [fn["fwd"](params, s, jnp.int32(i), x) for x in xs]
    return params


def balance_rows(m: Dict, key: jax.Array):
    """The token rows (1, ``max_position_embeddings``) the router bias is
    balanced on, each from a Markov stream of its own seeded from
    ``key``."""
    seeds = jax.random.randint(jax.random.fold_in(key, 1), (BALANCE_ROWS,),
                               0, 2**31 - 1)
    return [jnp.asarray(feed.tokens(feed.MarkovLM(
        m["vocab_size"], m["max_position_embeddings"], int(s)), 0,
        [0])["tokens"]) for s in seeds]


@functools.partial(jax.jit, static_argnums=(1,))
def _balanced_bias(scores, k: int):
    """A bias (e,) under which the top-k of scores (t, e) + bias give
    every expert t k / e picks, near enough: DeepSeek-V3's update, each
    round moving every expert's bias a step towards its share (up when
    it has fewer picks, down when more), from minus the experts' mean
    scores, the step shrinking 3% a round from half the scores'
    standard deviation."""
    t, e = scores.shape
    step0 = 0.5 * jnp.std(scores)

    def round_(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b + step0 * 0.97 ** i * jnp.sign(t * k / e - load)

    return jax.lax.fori_loop(0, BALANCE_ROUNDS, round_,
                             -jnp.mean(scores, axis=0))


# ------------------------------------------------------------ forward
def _attend(prec: str, q, k, v, start: int):
    """Causal attention of the query block starting at ``start``: q (b,
    c, h, dqk), k (b, l, h, dqk), v (b, l, h, dv) -> (b, c, h, dv)."""
    c, l = q.shape[1], k.shape[1]
    s = ref.mm("bqhd,bkhd->bhqk", q, k, prec) / math.sqrt(q.shape[-1])
    seen = (jnp.arange(l)[None, :]
            <= start + jnp.arange(c)[:, None])[None, None]
    p = jax.nn.softmax(jnp.where(seen, s, ref.NEG_INF), axis=-1)
    return ref.mm("bhqk,bkhd->bqhd", p, v, prec)


def mla(m: Dict, prec: str, x, a):
    """Latent attention on x (b, l, d) float32 (``repro.models.mla``'s
    equations, written out)."""
    b, l, _ = x.shape
    nope, r = m["qk_nope_head_dim"], m["kv_lora_rank"]
    theta, mm = m["rope_theta"], ref.mm
    q = mm("bld,dhk->blhk", x, a["wq"], prec)
    ckr = mm("bld,dr->blr", x, a["wkv_a"], prec)
    c = ref.rms(ckr[..., :r], a["kv_norm"], m["rms_norm_eps"])
    kv = mm("blr,rhk->blhk", c, a["wkv_b"], prec)
    k_rope = ref.rope(ckr[..., None, r:], l, theta)
    q = jnp.concatenate([q[..., :nope], ref.rope(q[..., nope:], l, theta)],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + (k_rope.shape[-1],))], axis=-1)
    v = kv[..., nope:]
    blk = Q_BLOCK if l > Q_BLOCK and l % Q_BLOCK == 0 else l
    qs = q.reshape(b, l // blk, blk, *q.shape[2:]).swapaxes(0, 1)
    part = jax.checkpoint(
        lambda qb, i: _attend(prec, qb, k, v, i * blk))
    o = jax.lax.map(lambda xs: part(*xs), (qs, jnp.arange(l // blk)))
    o = o.swapaxes(0, 1).reshape(b, l, *o.shape[3:])
    return mm("blhk,hkd->bld", o, a["wo"], prec)


def _swiglu(prec: str, h, w, prefix: str = "w_"):
    mm = ref.mm
    act = (jax.nn.silu(mm("...d,df->...f", h, w[prefix + "gate"], prec))
           * mm("...d,df->...f", h, w[prefix + "up"], prec))
    return mm("...f,fd->...d", act, w[prefix + "down"], prec)


def router_scores(prec: str, h, router):
    """Sigmoid scores (b, l, e) of every routed expert."""
    return jax.nn.sigmoid(ref.mm("bld,de->ble", h, router, prec))


def route(m: Dict, prec: str, h, router, bias):
    """The expert weights each held expert gets per token, (b, l, held):
    sigmoid scores over every expert, the top-k of scores + bias picked,
    weighed by their scores renormalised and times the scaling factor;
    zero where a held expert is not picked."""
    scores = router_scores(prec, h, router)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32),
                           m["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True) \
        * m["routed_scaling_factor"]
    held = m["held_start"] + jnp.arange(m["n_routed_experts"])
    return jnp.sum(jnp.where(idx[..., None] == held, gate[..., None], 0.0),
                   axis=-2)


def experts(m: Dict, prec: str, h, u):
    """The held experts' part, each run over every token and weighed by
    its router weight, plus the shared experts."""
    weight = route(m, prec, h, u["router"], u["router_bias"])
    y = _swiglu(prec, h, u, "shared_")
    for e in range(m["n_routed_experts"]):
        w_e = {k: u[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + weight[..., e:e + 1] * _swiglu(prec, h, w_e)
    return y


def _attend_half(m: Dict, prec: str, x, w):
    """x (b, l, d) float32 after the block's latent attention, and its
    norm that the MLP or expert layer takes."""
    eps = m["rms_norm_eps"]
    x = x + mla(m, prec, ref.rms(x, w["attn_norm"]["scale"], eps),
                w["attn"])
    return x, ref.rms(x, w["mlp_norm"]["scale"], eps)


def layer(m: Dict, prec: str, x, w):
    """One pre-norm block on x (b, l, d) float32: latent attention, then
    the dense MLP or the expert layer."""
    x, h = _attend_half(m, prec, x, w)
    if "moe" in w:
        return x + experts(m, prec, h, w["moe"])
    return x + _swiglu(prec, h, w["mlp"])


#: The layer stacks in the order they run.
STACKS = ("dense_layers", "layers")


@functools.lru_cache(maxsize=None)
def _fns(mkey: Tuple, prec: str):
    """Jitted pieces for one configuration and precision (the layer
    index is traced, so one compile serves every layer of a stack)."""
    m = dict(mkey)

    def layer_w(params, stack, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False).astype(jnp.float32), params[stack])

    @functools.partial(jax.jit, static_argnums=(1,))
    def fwd(params, stack, i, x):
        return layer(m, prec, x, layer_w(params, stack, i))

    @functools.partial(jax.jit, static_argnums=(1,))
    def bwd(params, stack, i, x, dy):
        _, vjp = jax.vjp(lambda x_, w_: layer(m, prec, x_, w_), x,
                         layer_w(params, stack, i))
        return vjp(dy)

    @jax.jit
    def scores(params, i, x):
        w = layer_w(params, "layers", i)
        return router_scores(prec, _attend_half(m, prec, x, w)[1],
                             w["moe"]["router"]).reshape(-1, m[
                                 "router_outputs"])

    dense = _dense._fns(mkey, prec)
    return dict(fwd=fwd, bwd=bwd, scores=scores,
                **{k: dense[k] for k in ("embed", "head", "head_fwd",
                                         "add_layer", "add_embed")})


def loss_and_grad(m: Dict, params, tokens, labels, prec: str = "f32",
                  with_grad: bool = True, half: bool = False):
    """Mean next-token loss over every token of the batch and, with
    ``with_grad``, its float32 gradient as a tree like ``params``; row
    by row and layer by layer.  ``prec='fp8'`` is the control
    (``reference.cast``); ``half`` leaves out the second half of the
    batch's tokens and takes the mean over the rest, a fault."""
    fn = _fns(ref.hashable(m), prec)
    order = [(s, i) for s in STACKS if s in params
             for i in range(jax.tree_util.tree_leaves(params[s])[0]
                            .shape[0])]
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    if half:
        kept = jnp.arange(labels.size).reshape(labels.shape) \
            < labels.size // 2
        labels = jnp.where(kept, labels, -1)
    norm = jnp.float32(jnp.sum(labels >= 0))
    total = 0.0
    grads = (jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
        if with_grad else None)
    for r in range(tokens.shape[0]):
        toks, labs = tokens[r:r + 1], labels[r:r + 1]
        xs = [fn["embed"](params, toks)]
        for s, i in order:
            xs.append(fn["fwd"](params, s, jnp.int32(i), xs[-1]))
        if not with_grad:
            total += float(fn["head_fwd"](params, xs[-1], labs, norm))
            continue
        loss, (dx, dfin, dun) = fn["head"](params, xs[-1], labs, norm)
        total += float(loss)
        grads["final_norm"]["scale"] = grads["final_norm"]["scale"] + dfin
        grads["unembed"] = grads["unembed"] + dun
        xs.pop()
        for s, i in reversed(order):
            dx, dw = fn["bwd"](params, s, jnp.int32(i), xs.pop(), dx)
            grads[s] = fn["add_layer"](grads[s], jnp.int32(i), dw)
        grads["embed"] = fn["add_embed"](grads["embed"], toks, dx)
    return total, grads


# ------------------------------------------------------------ counts
def _attn_matmul_params(m: Dict) -> int:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dqk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * h * dqk + d * (r + m["qk_rope_head_dim"])
            + r * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_params(m: Dict) -> int:
    """Parameters of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expected_held_rows(m: Dict) -> float:
    """Rows a token sends to the experts held here, on average: k of the
    router's experts, of which these are a share."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["router_outputs"])


def matmul_params(m: Dict) -> float:
    """Parameters a token's matmuls take: attention, the dense MLP, the
    router, the shared experts, the held experts at the expected rows,
    and the unembedding (the embedding is a gather)."""
    d = m["hidden_size"]
    nd = m["first_k_dense_replace"]
    ne = m["num_hidden_layers"] - nd
    per_expert_layer = (d * m["router_outputs"]
                        + m["n_shared_experts"] * expert_params(m)
                        + expected_held_rows(m) * expert_params(m))
    return (m["num_hidden_layers"] * _attn_matmul_params(m)
            + nd * 3 * d * m["intermediate_size"]
            + ne * per_expert_layer + m["vocab_size"] * d)


def param_count(m: Dict) -> int:
    """Every parameter: the weights, the router bias, the norms (two a
    layer, the latent's, the final), the embedding and unembedding."""
    d, n = m["hidden_size"], m["num_hidden_layers"]
    nd = m["first_k_dense_replace"]
    ne = n - nd
    return (n * (_attn_matmul_params(m) + m["kv_lora_rank"] + 2 * d)
            + nd * 3 * d * m["intermediate_size"]
            + ne * (d * m["router_outputs"] + m["router_outputs"]
                    + (m["n_routed_experts"] + m["n_shared_experts"])
                    * expert_params(m))
            + 2 * m["vocab_size"] * d + d)


def attn_pair_flops(m: Dict) -> float:
    """FLOPs of one attended (query, key) pair of one layer's forward:
    QK^T over the q/k width and PV over the v width, every head."""
    return 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x forward),
    nothing recomputed counted, routed experts at the expected rows,
    causal attention by the pairs it attends."""
    attn = attn_pair_flops(m) * peaks.attended_pairs(seq, None) / seq
    return 3.0 * (2.0 * matmul_params(m) + m["num_hidden_layers"] * attn)


def attn_costs(batch: int, seq: int, m: Dict, itemsize: int):
    """((FLOPs, bytes) of one causal latent-attention forward call, the
    same of its backward) over one layer: the forward reads q, k, v and
    writes o; the backward recomputes QK^T and makes dV, dP, dQ and dK
    (two matmuls over the v width, three over the q/k width), reads q,
    k, v, o, dO and writes dq, dk, dv."""
    h = m["num_attention_heads"]
    dqk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dv = m["v_head_dim"]
    pairs = batch * h * peaks.attended_pairs(seq, None)
    rows = float(itemsize * batch * seq * h)
    return ((2.0 * (dqk + dv) * pairs, rows * 2 * (dqk + dv)),
            (2.0 * (3 * dqk + 2 * dv) * pairs, rows * 4 * (dqk + dv)))


def expert_costs(m: Dict, rows: float, layer_steps: float,
                 itemsize: int):
    """(FLOPs, bytes) of the held experts' grouped matmuls as a step
    runs them, over ``rows`` assignments and ``layer_steps`` layer-steps:
    the three matmuls (d -> f twice, f -> d) in the forward and again in
    its recompute, and their two transposes (input and weight gradient)
    in the backward, 12 grouped matmuls a layer-step.  Each reads its
    rows and the held weights and writes its rows."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    e = m["n_routed_experts"]
    flops = 12 * 2.0 * rows * d * f
    nbytes = 4 * 3 * itemsize * (rows * (d + f) + layer_steps * e * d * f)
    return flops, float(nbytes)
