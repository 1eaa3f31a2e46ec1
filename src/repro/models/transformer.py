"""Dense decoder-only transformer (llama / qwen / mistral / chameleon /
deepseek-v3 families) with scan-over-layers, GQA(+SWA) or latent
attention (mla.py), optional QKV bias and qk-norm, SwiGLU MLP, an
optional MoE FFN (see moe.py) and optional leading dense layers.

Three entry points per model:
  ``forward``        (b, l) tokens -> (b, l, v) logits        [train/prefill]
  ``forward_prefill``  also returns the populated KV cache     [serving]
  ``forward_decode``  (b, 1) token + cache -> logits + cache   [serving]
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.models.sharding import shard


# --------------------------------------------------------------- param defs
def attn_defs(cfg: ModelConfig, n: int) -> Dict[str, ParamDef]:
    d, hq, hkv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.resolved_head_dim)
    defs: Dict[str, ParamDef] = {
        "wq": ParamDef((n, d, hq, hd), (None, "fsdp", "heads", None),
                       fan_in_dims=(1,)),
        "wk": ParamDef((n, d, hkv, hd), (None, "fsdp", "kv_heads", None),
                       fan_in_dims=(1,)),
        "wv": ParamDef((n, d, hkv, hd), (None, "fsdp", "kv_heads", None),
                       fan_in_dims=(1,)),
        "wo": ParamDef((n, hq, hd, d), (None, "heads", None, "fsdp"),
                       fan_in_dims=(1, 2)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((n, hq, hd), (None, "heads", None),
                              init="zeros")
        defs["bk"] = ParamDef((n, hkv, hd), (None, "kv_heads", None),
                              init="zeros")
        defs["bv"] = ParamDef((n, hkv, hd), (None, "kv_heads", None),
                              init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((n, hd), (None, None), init="ones")
        defs["k_norm"] = ParamDef((n, hd), (None, None), init="ones")
    return defs


def mlp_defs(cfg: ModelConfig, n: int) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act == "silu":
        return {
            "w_gate": ParamDef((n, d, f), (None, "fsdp", "model"),
                               fan_in_dims=(1,)),
            "w_up": ParamDef((n, d, f), (None, "fsdp", "model"),
                             fan_in_dims=(1,)),
            "w_down": ParamDef((n, f, d), (None, "model", "fsdp"),
                               fan_in_dims=(1,)),
        }
    return {
        "w_up": ParamDef((n, d, f), (None, "fsdp", "model"), fan_in_dims=(1,)),
        "b_up": ParamDef((n, f), (None, "model"), init="zeros"),
        "w_down": ParamDef((n, f, d), (None, "model", "fsdp"),
                           fan_in_dims=(1,)),
        "b_down": ParamDef((n, d), (None, None), init="zeros"),
    }


def norm_defs(cfg: ModelConfig, n: int) -> Dict[str, ParamDef]:
    d = cfg.d_model
    defs = {"scale": ParamDef((n, d), (None, None), init="ones")}
    if cfg.norm == "layernorm":
        defs["bias"] = ParamDef((n, d), (None, None), init="zeros")
    return defs


def _block_defs(cfg: ModelConfig, n: int, moe: bool) -> Dict[str, Any]:
    block: Dict[str, Any] = {
        "attn": mla.mla_defs(cfg, n) if cfg.mla else attn_defs(cfg, n),
        "attn_norm": norm_defs(cfg, n),
        "mlp_norm": norm_defs(cfg, n),
    }
    if moe:
        block["moe"] = moe_lib.moe_defs(cfg, n)
    else:
        block["mlp"] = mlp_defs(cfg, n)
    return block


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """``layers`` stacks the uniform blocks; a config with leading dense
    layers (``first_k_dense``) stacks those, with a ``d_ff`` MLP, under
    ``dense_layers``."""
    defs: Dict[str, Any] = {
        "embed": ParamDef((cfg.padded_vocab, cfg.d_model), ("model", "fsdp"),
                          init="embed", fan_in_dims=(1,)),
        "final_norm": _unstack_norm(cfg),
        "layers": _block_defs(cfg, cfg.n_scanned_layers,
                              cfg.moe is not None),
    }
    if cfg.first_k_dense:
        defs["dense_layers"] = _block_defs(cfg, cfg.first_k_dense, False)
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.padded_vocab, cfg.d_model),
                                   ("model", "fsdp"), fan_in_dims=(1,))
    return defs


def _unstack_norm(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d = cfg.d_model
    defs = {"scale": ParamDef((d,), (None,), init="ones")}
    if cfg.norm == "layernorm":
        defs["bias"] = ParamDef((d,), (None,), init="zeros")
    return defs


# --------------------------------------------------------------- layer body
def _ffn(cfg: ModelConfig, h: jax.Array, w: Dict[str, Any],
         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The block's MLP or expert layer: (out, aux) where aux holds the
    ``aux_loss`` and, for the held-expert layer, its counts."""
    if "moe" not in w:
        return L.mlp_block(cfg, h, w["mlp"]), {
            "aux_loss": jnp.zeros((), jnp.float32)}
    if cfg.moe.router == "sigmoid_noaux":
        out, counts = moe_lib.held_moe_block(cfg, h, w["moe"])
        return out, dict(counts, aux_loss=jnp.zeros((), jnp.float32))
    out, aux = moe_lib.moe_block(cfg, h, w["moe"])
    return out, {"aux_loss": aux}


def _layer(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
           cos: jax.Array, sin: jax.Array, mask: jax.Array,
           collect_kv: bool = False):
    """Pre-norm residual block. Returns (x, aux[, (k, v)]), aux as
    ``_ffn`` gives it."""
    # pin the carry layout: without this GSPMD propagates whatever layout
    # the embed gather preferred into the scan carry and re-shards every
    # dot (measured: 671 MB activation all-gathers per layer, §Perf it.1)
    x = shard(x, "batch", "seq", None)
    h = L.apply_norm(cfg, x, w["attn_norm"])
    attend = mla.attention_block if cfg.mla else L.attention_block
    att = attend(cfg, h, w["attn"], cos, sin, mask, collect_kv=collect_kv)
    kv = None
    if collect_kv:
        att, kv = att
    # fused residual-add + norm (registry residual_rmsnorm): one pass
    # produces the updated stream AND its normed view for the MLP
    x, h = L.residual_apply_norm(cfg, att, x, w["mlp_norm"])
    out, aux = _ffn(cfg, h, w)
    if collect_kv:
        return x + out, aux, kv
    return x + out, aux


def _stacks(params: Dict[str, Any]):
    """The stacked layer subtrees in the order they run."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


def _sum_aux(auxs) -> Dict[str, jax.Array]:
    """The stacks' aux dicts summed key by key."""
    out: Dict[str, jax.Array] = {}
    for aux in auxs:
        for k, v in aux.items():
            out[k] = out[k] + v if k in out else v
    return out


def _scan_layers(cfg: ModelConfig, x: jax.Array, layer_params: Any,
                 body) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """lax.scan over stacked layer params with optional remat; the aux
    summed over the layers."""
    if cfg.remat == "full":
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, layer_params, unroll=cfg.scan_unroll)
    return x, jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), auxs)


def _rope(cfg: ModelConfig, l: int) -> Tuple[jax.Array, jax.Array]:
    dim = cfg.qk_rope_head_dim if cfg.mla else cfg.resolved_head_dim
    return L.rotary_embedding(jnp.arange(l), dim, cfg.rope_theta)


# --------------------------------------------------------------- forward
def hidden(cfg: ModelConfig, params: Dict[str, Any], tokens: jax.Array,
           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (b, l) -> the final-normed hidden states (b, l, d) and the
    layers' aux summed over them (``_ffn``)."""
    l = tokens.shape[1]
    x = L.embed(tokens, params["embed"]).astype(jnp.dtype(cfg.dtype))
    cos, sin = _rope(cfg, l)
    mask = L.causal_window_mask(l, l, window=cfg.sliding_window)
    body = functools.partial(_layer, cfg, cos=cos, sin=sin, mask=mask)
    auxs = []
    for stack in _stacks(params):
        x, aux = _scan_layers(cfg, x, stack, lambda c, w: body(c, w))
        auxs.append(aux)
    return L.apply_norm(cfg, x, params["final_norm"]), _sum_aux(auxs)


def forward(cfg: ModelConfig, params: Dict[str, Any], tokens: jax.Array,
            ) -> Tuple[jax.Array, jax.Array]:
    """Training forward. tokens (b, l) -> logits (b, l, v), aux loss."""
    x, aux = hidden(cfg, params, tokens)
    return L.unembed(x, _table(cfg, params), cfg.vocab_size), aux["aux_loss"]


def _table(cfg: ModelConfig, params: Dict[str, Any]) -> jax.Array:
    return params["embed"] if cfg.tie_embeddings else params["unembed"]


def forward_prefill(cfg: ModelConfig, params: Dict[str, Any],
                    tokens: jax.Array,
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Serving prefill: last-position logits + populated KV cache.

    Only the final position is unembedded (the rest would be dead code in
    a real serving stack); the per-layer post-rotary K/V are stacked into
    the decode cache layout (n_layers, b, l, hkv, hd)."""
    b, l = tokens.shape
    x = L.embed(tokens, params["embed"]).astype(jnp.dtype(cfg.dtype))
    cos, sin = _rope(cfg, l)
    mask = L.causal_window_mask(l, l, window=cfg.sliding_window)

    quantized = cfg.kv_cache_dtype == "int8"

    def body(carry, w):
        y, _, (k, v) = _layer(cfg, carry, w, cos, sin, mask,
                              collect_kv=True)
        if quantized:
            kq, ks_ = L.quantize_kv(k)
            vq, vs_ = L.quantize_kv(v)
            return y, (kq, ks_, vq, vs_)
        return y, (k.astype(jnp.dtype(cfg.dtype)),
                   v.astype(jnp.dtype(cfg.dtype)))

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    outs = []
    for stack in _stacks(params):
        x, kv = jax.lax.scan(body, x, stack, unroll=cfg.scan_unroll)
        outs.append(kv)
    kv_out = tuple(jnp.concatenate(parts) for parts in zip(*outs))
    x = L.apply_norm(cfg, x[:, -1:], params["final_norm"])
    logits = L.unembed(x, _table(cfg, params), cfg.vocab_size)
    if quantized:
        kq, ks_, vq, vs_ = kv_out
        return logits, {"k": kq, "k_scale": ks_, "v": vq, "v_scale": vs_}
    ks, vs = kv_out
    return logits, {"k": ks, "v": vs}


def loss_fn(cfg: ModelConfig, params: Dict[str, Any],
            batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, Any]]:
    """(loss, metrics): the NLL plus the weighted aux loss; metrics hold
    ``loss`` (the NLL), ``aux_loss`` and, for a held-expert model,
    ``counts``: ``{"moe": its routing counts summed over the layers}``,
    by ``repro.perfcount`` group."""
    x, aux = hidden(cfg, params, batch["tokens"])
    nll = L.cross_entropy(L.unembed(x, _table(cfg, params), cfg.vocab_size),
                          batch["labels"])
    weight = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    metrics: Dict[str, Any] = {"loss": nll, "aux_loss": aux["aux_loss"]}
    counts = {k: v for k, v in aux.items() if k != "aux_loss"}
    if counts:
        metrics["counts"] = {"moe": counts}
    return nll + weight * aux["aux_loss"], metrics


# --------------------------------------------------------------- serving
def _cache_shapes(cfg: ModelConfig, batch: int, max_seq: int):
    """(k shape, v shape) of the stacked per-layer cache.  SWA models cap
    the ring at the window; latent attention keeps k and v per head."""
    if cfg.sliding_window is not None:
        max_seq = min(max_seq, cfg.sliding_window)
    lead = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads)
    if cfg.mla:
        return lead + (cfg.qk_head_dim,), lead + (cfg.v_head_dim,)
    return (lead + (cfg.resolved_head_dim,),) * 2


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype: Optional[str] = None) -> Dict[str, jax.Array]:
    """Stacked per-layer KV cache. SWA models cap the ring at the window;
    ``cfg.kv_cache_dtype == 'int8'`` stores quantized K/V with
    per-(token, head) f32 scales (layers.quantize_kv)."""
    kshape, vshape = _cache_shapes(cfg, batch, max_seq)
    if cfg.kv_cache_dtype == "int8":
        return {"k": jnp.zeros(kshape, jnp.int8),
                "v": jnp.zeros(vshape, jnp.int8),
                "k_scale": jnp.zeros(kshape[:-1], jnp.float32),
                "v_scale": jnp.zeros(vshape[:-1], jnp.float32)}
    dt = jnp.dtype(dtype or cfg.dtype)
    return {"k": jnp.zeros(kshape, dt), "v": jnp.zeros(vshape, dt)}


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int, rules,
                dtype: Optional[str] = None) -> Dict[str, Any]:
    from jax.sharding import PartitionSpec as P
    axes = (None, "batch", "cache_seq", None, None)
    out = {}
    for name, shape in zip(("k", "v"), _cache_shapes(cfg, batch, max_seq)):
        out[name] = P() if rules is None else rules.spec(axes, shape)
        if cfg.kv_cache_dtype == "int8":
            out[f"{name}_scale"] = (P() if rules is None
                                    else rules.spec(axes[:-1], shape[:-1]))
    return out


def forward_decode(cfg: ModelConfig, params: Dict[str, Any],
                   token: jax.Array, cache: Dict[str, jax.Array],
                   index: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. token (b, 1); cache leaves (n_layers, ...), the
    leading dense layers first."""
    x = L.embed(token, params["embed"]).astype(jnp.dtype(cfg.dtype))
    keys = sorted(cache)  # k, k_scale?, v, v_scale?
    attend = mla.decode_block if cfg.mla else L.decode_attention_block

    def body(carry, xs):
        w = xs[0]
        layer_cache = dict(zip(keys, xs[1:]))
        h = L.apply_norm(cfg, carry, w["attn_norm"])
        att, new_cache = attend(cfg, h, w["attn"], layer_cache, index)
        y = carry + att
        h = L.apply_norm(cfg, y, w["mlp_norm"])
        out, _ = _ffn(cfg, h, w)
        return y + out, tuple(new_cache[k] for k in keys)

    start, outs = 0, []
    for stack in _stacks(params):
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        xs = (stack,) + tuple(cache[k][start:start + n] for k in keys)
        x, new_leaves = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
        outs.append(new_leaves)
        start += n
    new_cache = {k: jnp.concatenate(parts)
                 for k, parts in zip(keys, zip(*outs))}
    x = L.apply_norm(cfg, x, params["final_norm"])
    return L.unembed(x, _table(cfg, params), cfg.vocab_size), new_cache
