"""Multi-head latent attention (DeepSeek-V2/V3 MLA), q without a LoRA.

    q            = x Wq, per head [q_nope (nope), q_rope (rope)]
    [c, k_rope]  = x Wkv_a;  c <- RMSNorm(c)          (c: kv_lora_rank)
    [k_nope, v]  = c Wkv_b, per head
    q_rope, k_rope rotated; k_rope is one per token, shared by the heads
    k            = [k_nope, k_rope]
    out          = attention(q, k, v) Wo,  scores scaled by 1/sqrt(nope + rope)

The q/k heads are ``qk_nope_head_dim + qk_rope_head_dim`` wide and the v
heads ``v_head_dim``, so attention runs with v narrower than q/k
(``kernels.registry.attention`` takes both).  The rope rotates the two
halves of the rope part together (``layers.apply_rotary``), not the
interleaved pairs of the HF DeepSeek-V3 code: the two differ by a fixed
permutation of the rope columns of ``wq`` and ``wkv_a``, which a loaded
checkpoint would take on the way in.

Decode keeps the ordinary KV cache with k and v expanded per head (k
``nope + rope`` wide, v ``v_head_dim``), not the compressed latent.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.models.sharding import shard


def mla_defs(cfg: ModelConfig, n: int) -> Dict[str, ParamDef]:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    return {
        "wq": ParamDef((n, d, h, nope + rope), (None, "fsdp", "heads", None),
                       fan_in_dims=(1,)),
        "wkv_a": ParamDef((n, d, r + rope), (None, "fsdp", None),
                          fan_in_dims=(1,)),
        "kv_norm": ParamDef((n, r), (None, None), init="ones"),
        "wkv_b": ParamDef((n, r, h, nope + dv), (None, None, "heads", None),
                          fan_in_dims=(1,)),
        "wo": ParamDef((n, h, dv, d), (None, "heads", None, "fsdp"),
                       fan_in_dims=(1, 2)),
    }


def qkv(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any], cos: jax.Array,
        sin: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (b, l, d) -> q, k (b, l, h, nope + rope), v (b, l, h, v_head_dim),
    rotated by ``cos``/``sin`` ((l, rope/2) or (b, l, rope/2))."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bld,dhk->blhk", x, w["wq"])
    ckr = jnp.einsum("bld,dr->blr", x, w["wkv_a"])
    c = L.rms_norm(ckr[..., :r], w["kv_norm"], eps=cfg.rms_norm_eps,
                   kernels=cfg.kernels)
    kv = jnp.einsum("blr,rhk->blhk", c, w["wkv_b"])
    k_rope = L.apply_rotary(ckr[..., None, r:], cos, sin)     # (b, l, 1, rope)
    q = jnp.concatenate(
        [q[..., :nope], L.apply_rotary(q[..., nope:], cos, sin)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + (k_rope.shape[-1],))], axis=-1)
    return q, k, kv[..., nope:]


def attention_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
                    cos: jax.Array, sin: jax.Array, mask: jax.Array, *,
                    collect_kv: bool = False):
    """The latent-attention sublayer on the training / prefill path; as
    ``layers.attention_block`` (the same mask contract, the same
    ``collect_kv``)."""
    q, k, v = qkv(cfg, x, w, cos, sin)
    out = L.attention(cfg, L.shard_attn_q(q), k, v, mask=mask,
                      causal_structure=(True, cfg.sliding_window))
    out = shard(jnp.einsum("blhk,hkd->bld", out, w["wo"]),
                "batch", None, None)
    if collect_kv:
        return out, (k, v)
    return out


def decode_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
                 cache: Dict[str, jax.Array], index: jax.Array,
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token x (b, 1, d) at position ``index`` through the
    per-head KV cache; as ``layers.decode_attention_block``."""
    pos = jnp.full((x.shape[0], 1), index, jnp.int32)
    cos, sin = L.rotary_embedding(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
    q, k, v = qkv(cfg, x, w, cos, sin)
    out, new_cache = L.cache_attention(cfg, q, k, v, cache, index)
    out = shard(jnp.einsum("blhk,hkd->bld", out, w["wo"]),
                "batch", None, None)
    return out, new_cache
