"""Shared neural building blocks.

The worker-step hot ops (attention, RMSNorm, fused residual+RMSNorm)
route through ``repro.kernels.registry`` — enum dispatch over
Pallas/XLA variants selected by ``cfg.kernels`` — on the unsharded
path; the mesh-sharded SP/TP formulations below stay XLA (their layout
pins are the point, see EXPERIMENTS.md §Perf).

Conventions:
  activations   (batch, seq, d_model)                 bf16/f32
  q/k/v         (batch, seq, heads, head_dim)
  KV cache      (batch, max_seq, kv_heads, head_dim)  — 'cache_seq' sharded
  softmax/norm accumulation always float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry as K
from repro.models.config import ModelConfig
from repro.models.sharding import shard

NEG_INF = -1e30  # large-but-finite: -inf breaks softmax rows that are fully masked


# ----------------------------------------------------------------- norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             kernels: str = "auto") -> jax.Array:
    """Registry-dispatched RMSNorm (``kernels`` = ``cfg.kernels``)."""
    return K.rmsnorm(x, weight, eps=eps, kernels=kernels)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, x: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, w["scale"], eps=cfg.rms_norm_eps,
                        kernels=cfg.kernels)
    return layer_norm(x, w["scale"], w["bias"])


def residual_apply_norm(cfg: ModelConfig, delta: jax.Array, x: jax.Array,
                        w: Dict[str, jax.Array],
                        ) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm block glue: ``(x + delta, norm(x + delta))``.

    For rmsnorm this is the registry's fused residual+RMSNorm op (one
    VMEM pass on the Pallas variant); layernorm keeps the unfused form.
    """
    if cfg.norm == "rmsnorm":
        return K.residual_rmsnorm(delta, x, w["scale"],
                                  eps=cfg.rms_norm_eps, kernels=cfg.kernels)
    s = x + delta
    return s, layer_norm(s, w["scale"], w["bias"])


# ----------------------------------------------------------------- rotary
def rotary_embedding(positions: jax.Array, head_dim: int,
                     theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin (..., head_dim/2), float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (b, s, h, d); cos/sin (b, s, d/2) or (s, d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:           # (s, d/2) -> broadcast over batch/heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:                       # (b, s, d/2)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def causal_window_mask(lq: int, lk: int, *, q_offset: int = 0,
                       window: Optional[int] = None) -> jax.Array:
    """(lq, lk) bool mask: True = attend. Causal plus optional sliding
    window of width ``window`` (inclusive of self)."""
    qpos = jnp.arange(lq)[:, None] + q_offset
    kpos = jnp.arange(lk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _attention_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                       mask: jax.Array, pin=None) -> jax.Array:
    """Grouped GQA: q (b, lq, hkv, g, d); k/v (b, lk, hkv, d).

    Used for decode (lq == 1: tiny scores; the KV cache keeps its own
    cache_seq sharding) and for SP-mode training (scores
    (b, hkv, g, lq, lk) pinned seq-sharded on lq via ``pin``)."""
    d = q.shape[-1]
    scores = jnp.einsum("blhgd,bmhd->bhglm", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if pin is not None:
        scores = shard(scores, *pin)
    if mask.ndim == 2:
        m = mask[None, None, None, :, :]
    else:
        m = mask[:, None, None, :, :]
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if pin is not None:
        probs = shard(probs, *pin)
    out = jnp.einsum("bhglm,bmhd->blhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _attention_heads(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array) -> jax.Array:
    """Train-path attention over full query heads: q/k/v (b, l, hq, d).

    KV were pre-broadcast to hq heads so the (lq × lk) score tensor
    shards on the head axis — the GQA (hkv, g) factored form would cap
    score sharding at hkv (< mesh 'model' size for most assigned archs)
    and GSPMD would materialize near-replicated multi-GiB score blocks.

    The score/prob/out layouts are pinned (heads over 'model', query-seq
    fallback): measured on the dry-run, leaving them to GSPMD's choice
    produced l-sharded scores plus 671 MB q/k head-gathers per layer
    (§Perf iteration 2).
    """
    d = q.shape[-1]
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    scores = _shard_scores(scores)
    m = mask[None, None] if mask.ndim == 2 else mask[:, None]
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = _shard_scores(probs)
    out = jnp.einsum("bhlm,bmhd->blhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = shard_attn_q(out)
    return out.astype(q.dtype)


def _shard_scores(s: jax.Array) -> jax.Array:
    """scores/probs (b, h, lq, lk): heads over 'model' when divisible,
    else query-seq over 'model' (the shard_attn_q fallback layout)."""
    from repro.models.sharding import current_rules
    r = current_rules()
    if r is None or r.mesh is None:
        return s
    spec = r.spec(("batch", "model", None, None), s.shape)
    if "model" not in jax.tree_util.tree_leaves(spec):
        spec = r.spec(("batch", None, "model", None), s.shape)
    return jax.lax.with_sharding_constraint(
        s, jax.sharding.NamedSharding(r.mesh, spec))


def attention(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
              *, mask: jax.Array,
              causal_structure: Optional[Tuple[bool, Optional[int]]] = None,
              ) -> jax.Array:
    """Grouped-query attention.

    q (b, lq, hq, d); k (b, lk, hkv, d); v (b, lk, hkv, dv) (dv = d
    but for latent attention); mask (lq, lk) or (b, lq, lk).  Returns
    (b, lq, hq, dv).  ``causal_structure`` = (causal, window)
    asserts that ``mask`` is exactly ``causal_window_mask(lq, lk,
    q_offset=lk-lq, window=window)`` — the structured form the kernel
    registry can dispatch on.

    Four paths:
      * decode (lq == 1): grouped (hkv, g) form, tiny scores (XLA);
      * unsharded (no mesh) with a structured mask: dispatched through
        ``repro.kernels.registry.attention`` per ``cfg.kernels`` — the
        Pallas flash kernel (kernels/flash_attention.py, native on TPU,
        interpret mode elsewhere) or the quadratic XLA formulation;
      * SP mode (sequence-parallel attention — the measured default,
        EXPERIMENTS.md §Perf it.9): queries/scores/outputs stay
        seq-sharded over 'model', heads unsharded, K/V gathered to full
        length (small: hkv heads) — no l<->h layout transitions and no
        wo psum;
      * TP mode: KV broadcast to hq heads so scores shard on heads; when
        ``cfg.attn_chunk`` divides lq, queries go through a lax.scan in
        chunks — same math with (chunk × lk) score blocks bounding live
        memory, but NOT an online softmax: each chunk still materializes
        its full score rows (that fusion is the registry's Pallas flash
        variant, which the sharded paths do not use).
    """
    b, lq, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    g = hq // hkv
    if lq == 1:
        out = _attention_grouped(q.reshape(b, lq, hkv, g, d), k, v, mask)
        return out.reshape(b, lq, hq, dv)

    if causal_structure is not None and not _has_mesh() and mask.ndim == 2:
        causal, window = causal_structure
        return K.attention(q, k, v, causal=causal, window=window,
                           kernels=cfg.kernels)

    if _sp_mode():
        # k/v gathered over l (they arrive seq-sharded), heads unsharded
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        qg = shard(q, "batch", "seq", None, None).reshape(b, lq, hkv, g, d)
        out = _attention_grouped(qg, k, v, mask,
                                 pin=("batch", None, None, "seq", None))
        return shard(out.reshape(b, lq, hq, dv), "batch", "seq", None, None)

    # TP mode: k/v gathered over l BEFORE the head broadcast -- otherwise
    # GSPMD hits "involuntary full rematerialization" resharding the
    # broadcast output (measured on qwen110, see EXPERIMENTS.md #Perf)
    k = shard(k, "batch", None, None, None)
    v = shard(v, "batch", None, None, None)
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    k = shard_attn_kv(k)
    v = shard_attn_kv(v)

    chunk = cfg.attn_chunk
    if mask.ndim != 2 or chunk <= 0 or lq <= chunk or lq % chunk:
        return _attention_heads(q, k, v, mask)

    n_chunks = lq // chunk
    q_chunks = q.reshape(b, n_chunks, chunk, hq, d).transpose(1, 0, 2, 3, 4)
    m_chunks = mask.reshape(n_chunks, chunk, mask.shape[1])

    def body(_, xs):
        qc, mc = xs
        return (), _attention_heads(qc, k, v, mc)

    _, out = jax.lax.scan(body, (), (q_chunks, m_chunks))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, lq, hq, dv)


def _sp_mode() -> bool:
    from repro.models.sharding import current_rules
    r = current_rules()
    return r is not None and getattr(r, "attn_mode", "tp") == "sp"


def _has_mesh() -> bool:
    from repro.models.sharding import current_rules
    r = current_rules()
    return r is not None and r.mesh is not None


def shard_attn_q(x: jax.Array) -> jax.Array:
    """q (b, l, hq, d): SP mode -> seq-sharded; TP mode -> heads over
    'model' when divisible, else fall back to sequence(-query) sharding
    (qwen1.5-32b's 40 heads, whisper's 6 heads — DESIGN.md §6)."""
    from repro.models.sharding import current_rules
    r = current_rules()
    if r is None or r.mesh is None:
        return x
    if getattr(r, "attn_mode", "tp") == "sp":
        spec = r.spec(("batch", "seq", None, None), x.shape)
    else:
        spec = r.spec(("batch", None, "model", None), x.shape)
        if "model" not in jax.tree_util.tree_leaves(spec):
            spec = r.spec(("batch", "model", None, None), x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(r.mesh, spec))


def shard_attn_kv(x: jax.Array) -> jax.Array:
    """k/v post-broadcast (b, l, hq, d): heads over 'model' when they
    divide; otherwise replicated (queries carry the seq sharding)."""
    from repro.models.sharding import current_rules
    r = current_rules()
    if r is None or r.mesh is None:
        return x
    spec = r.spec(("batch", None, "model", None), x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(r.mesh, spec))


def attention_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
                    cos: jax.Array, sin: jax.Array,
                    mask: jax.Array, *, collect_kv: bool = False):
    """Full self-attention sublayer (training / prefill path).

    ``mask`` contract: every caller passes ``causal_window_mask(l, l,
    window=cfg.sliding_window)`` — asserted structurally to
    ``attention`` so the kernel registry can dispatch the flash
    variant.  With ``collect_kv`` also returns the post-rotary (k, v) —
    the prefill path stacks them into the serving KV cache."""
    b, l, _ = x.shape
    q = jnp.einsum("bld,dhk->blhk", x, w["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, w["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, w["wv"])
    if cfg.qkv_bias:
        q = q + w["bq"]
        k = k + w["bk"]
        v = v + w["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"], eps=cfg.rms_norm_eps,
                     kernels=cfg.kernels)
        k = rms_norm(k, w["k_norm"], eps=cfg.rms_norm_eps,
                     kernels=cfg.kernels)
    if cfg.use_rope:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    q = shard_attn_q(q)
    out = attention(cfg, q, k, v, mask=mask,
                    causal_structure=(True, cfg.sliding_window))
    out = jnp.einsum("blhk,hkd->bld", out, w["wo"])
    out = shard(out, "batch", None, None)
    if collect_kv:
        return out, (k, v)
    return out


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (..., hd) -> (int8 values, f32 per-row scale). Symmetric."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_attention_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
                           cache: Dict[str, jax.Array], index: jax.Array,
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode with in-place KV cache update.

    x (b, 1, d); cache {'k','v'} (b, S, hkv, hd) + ring semantics when the
    config has a sliding window smaller than S.  With
    ``cfg.kv_cache_dtype == 'int8'`` the cache carries quantized values
    plus per-(token, head) scales ('k_scale'/'v_scale', (b, S, hkv)).
    """
    b = x.shape[0]
    q = jnp.einsum("bld,dhk->blhk", x, w["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, w["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, w["wv"])
    if cfg.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"], eps=cfg.rms_norm_eps,
                     kernels=cfg.kernels)
        k = rms_norm(k, w["k_norm"], eps=cfg.rms_norm_eps,
                     kernels=cfg.kernels)
    if cfg.use_rope:
        pos = jnp.full((b, 1), index, jnp.int32)
        cos, sin = rotary_embedding(pos, cfg.resolved_head_dim,
                                    cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    out, new_cache = cache_attention(cfg, q, k, v, cache, index)
    out = jnp.einsum("blhk,hkd->bld", out, w["wo"])
    out = shard(out, "batch", None, None)
    return out, new_cache


def cache_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                    v: jax.Array, cache: Dict[str, jax.Array],
                    index: jax.Array,
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token's attention over the KV cache after writing its
    post-rotary k, v into slot ``index`` (mod the cache length): q (b,
    1, hq, d), k (b, 1, hkv, d), v (b, 1, hkv, dv) -> ((b, 1, hq, dv),
    the updated cache)."""
    s_max = cache["k"].shape[1]
    slot = index % s_max                      # ring slot (SWA caches)
    quantized = "k_scale" in cache
    if quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ck = jax.lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0))
        cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                           (0, slot, 0))
        cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                           (0, slot, 0))
        new_cache = {"k": shard(ck, "batch", "cache_seq", None, None),
                     "v": shard(cv, "batch", "cache_seq", None, None),
                     "k_scale": shard(cks, "batch", "cache_seq", None),
                     "v_scale": shard(cvs, "batch", "cache_seq", None)}
        ck = dequantize_kv(new_cache["k"], new_cache["k_scale"], q.dtype)
        cv = dequantize_kv(new_cache["v"], new_cache["v_scale"], q.dtype)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        ck = shard(ck, "batch", "cache_seq", None, None)
        cv = shard(cv, "batch", "cache_seq", None, None)
        new_cache = {"k": ck, "v": cv}

    # validity of each ring slot for the current query position: a slot
    # s was last written 'age' tokens ago (age = (cur_slot - s) mod S);
    # it holds a real token iff age <= index (cold start: slots "older"
    # than the stream are unwritten -- without this check, empty slots
    # attend as zero-vectors and corrupt the softmax).
    slots = jnp.arange(s_max)
    age = (index % s_max - slots) % s_max
    valid = age <= index
    if cfg.sliding_window is not None:
        valid &= age < cfg.sliding_window
    mask = jnp.broadcast_to(valid[None, :], (1, s_max))
    out = attention(cfg, q, ck, cv, mask=mask)
    # pin the head dim of the tiny (b, 1, hq, hd) activation so the wo
    # projection psums a ~200 KB partial instead of all-gathering the
    # full multi-GB wo weight (measured on mistral decode, §Perf it.11)
    return shard(out, "batch", None, "heads", None), new_cache


# ----------------------------------------------------------------- MLP
def mlp_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    if cfg.act == "silu":       # SwiGLU
        gate = jnp.einsum("bld,df->blf", x, w["w_gate"])
        up = jnp.einsum("bld,df->blf", x, w["w_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:                       # GELU (whisper)
        h = jnp.einsum("bld,df->blf", x, w["w_up"]) + w["b_up"]
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", None, "model")
    out = jnp.einsum("blf,fd->bld", h, w["w_down"])
    if cfg.act != "silu":
        out = out + w["b_down"]
    return shard(out, "batch", None, None)


# ----------------------------------------------------------------- embeddings
def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    out = jnp.take(table, tokens, axis=0)
    return shard(out, "batch", None, None)


def unembed(x: jax.Array, table: jax.Array,
            vocab_size: Optional[int] = None) -> jax.Array:
    """x (b, l, d) @ table^T (v_padded, d) -> logits (b, l, v_padded).

    Tables are padded so the vocab dim shards (config.padded_vocab);
    padded columns are masked to -1e30 (softmax weight 0, argmax-proof)."""
    logits = jnp.einsum("bld,vd->blv", x, table,
                        preferred_element_type=jnp.float32)
    v_padded = table.shape[0]
    if vocab_size is not None and vocab_size < v_padded:
        col = jnp.arange(v_padded)
        logits = jnp.where(col[None, None, :] < vocab_size, logits,
                           NEG_INF)
    return shard(logits, "batch", None, "model")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_id: int = -1) -> jax.Array:
    """Mean token NLL in f32; labels (b, l) with ignore_id masked out."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    weights = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * weights) / jnp.maximum(1.0, jnp.sum(weights))
