"""Moonlight-16B-A3B — DeepSeek-V3 layout: latent attention, a leading
dense layer, then 64 routed experts (top-6) + 2 shared a layer.

27L d_model=2048 16H; MLA with q unfactored (q_lora_rank null),
kv_lora_rank 512, q/k heads 128 + 64 rope, v heads 128; layer 0 a dense
SwiGLU of 11264, layers 1-26 experts of 1408 under the sigmoid router
with its selection bias (noaux_tc, one group, top-k renormalised, x
2.446); vocab 163840 untied; rope theta 50000; RMSNorm eps 1e-5.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B, config.json]

15,960,110,208 parameters by this layout (the router bias included).
The KV cache keeps k and v per head, not the compressed latent.
"""

from repro.models.config import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, first_k_dense=1,
    rope_theta=50_000.0, rms_norm_eps=1e-5,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  router="sigmoid_noaux", routed_scale=2.446,
                  aux_loss_weight=0.0),
)

#: The smoke layout: 1 dense + 2 expert layers, 16 experts (top-3) of
#: 32 and one shared, MLA heads of 16 + 8 rope (v 16) over a latent of 32.
SMOKE = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=160,
             vocab_size=256, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, dtype="float32",
             remat="none")
SMOKE_MOE = dict(n_experts=16, top_k=3, n_shared=1, d_expert=32)


def smoke_config() -> ModelConfig:
    import dataclasses
    return CONFIG.scaled(moe=dataclasses.replace(CONFIG.moe, **SMOKE_MOE),
                         **SMOKE)
