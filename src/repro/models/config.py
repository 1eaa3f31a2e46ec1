"""Model configuration shared by every architecture family."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int            # routed experts
    top_k: int
    n_shared: int = 0         # always-on shared experts (DeepSeek-MoE)
    d_expert: int = 0         # expert FFN width (0 -> use d_ff)
    capacity_factor: float = 1.25
    every: int = 1            # MoE on every k-th layer (Jamba: 2)
    aux_loss_weight: float = 0.01
    # "softmax": softmax scores, top-k renormalised, capacity dispatch
    # (moe.moe_block).  "sigmoid_noaux": DeepSeek-V3's sigmoid scores,
    # top-k picked on scores + a selection-only bias, no aux loss, and
    # the dropless held-expert layer (moe.held_moe_block).
    router: str = "softmax"
    routed_scale: float = 1.0   # multiplies the routed experts' weights
    # the routed experts this chip holds, [held_start, held_start + held)
    # of n_experts (0 -> all); the router still scores all n_experts
    held: int = 0
    held_start: int = 0

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                     # 0 -> d_model // n_heads
    # multi-head latent attention (DeepSeek-V2/V3), on when
    # kv_lora_rank > 0: k/v come from a kv_lora_rank-wide latent; q/k
    # heads are qk_nope_head_dim + qk_rope_head_dim wide, v heads
    # v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # attention
    qkv_bias: bool = False                # qwen1.5
    qk_norm: bool = False                 # chameleon
    sliding_window: Optional[int] = None  # h2o-danube SWA
    rope_theta: float = 10_000.0
    use_rope: bool = True                 # jamba/whisper: no rotary
    # MoE
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 0                # leading dense layers (d_ff wide)
    # hybrid (jamba): attention on layers where i % attn_period == attn_offset
    attn_period: int = 0
    attn_offset: int = 0
    # ssm
    ssm_kind: str = ""                    # "xlstm" | "mamba"
    slstm_layers: Tuple[int, ...] = ()    # xLSTM: which layers are sLSTM
    d_state: int = 16                     # mamba state dim
    d_conv: int = 4                       # mamba depthwise conv width
    expand: int = 2                       # mamba inner expansion
    # enc-dec (whisper)
    n_encoder_layers: int = 0
    # norm / glue
    norm: str = "rmsnorm"                 # rmsnorm | layernorm
    rms_norm_eps: float = 1e-6
    act: str = "silu"                     # silu (SwiGLU) | gelu
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: str = "full"                   # none | full  (scan-level remat)
    # chunking knobs: bound the materialized working set (HBM) without
    # changing the math; the cost-extraction mode of the dry-run disables
    # them so XLA's per-while cost under-count can be fixed by the
    # two-point depth fit (see roofline/analysis.py)
    attn_chunk: int = 512                 # 0 = full quadratic scores
    moe_chunk: int = 256                  # 0 = single dispatch
    mamba_chunk: int = 128                # 0 = single associative scan
    scan_unroll: bool = False             # unroll scan-over-layers (cost
                                          # extraction: while bodies are
                                          # cost-counted once by XLA)
    # optimizer selection for the training step (adafactor for the
    # largest models so optimizer state fits per-chip HBM; see DESIGN.md)
    optimizer: str = "adamw"
    # kernel variant selection for the worker-step hot ops, dispatched by
    # repro.kernels.registry ("auto" | variant | per-op overrides, see
    # repro.kernels.interface); validated upstream by api.spec
    kernels: str = "auto"
    # how the 'model' mesh axis is used: "tp" (tensor/expert parallel,
    # default) or "dp" (extra data parallelism + ZeRO param/opt sharding
    # -- the right choice for small models where 16-way TP is pure
    # overhead; measured 15x collective reduction on h2o-danube, §Perf)
    model_axis_role: str = "tp"
    # sequence-parallel attention (EXPERIMENTS.md §Perf it.9); ignored
    # when model_axis_role == "dp"
    sequence_parallel: bool = True
    # microbatch gradient accumulation: bounds the per-device activation
    # carry (remat saves one residual per layer per microbatch) so deep
    # models fit 16 GB/chip at global batch 256
    grad_accum: int = 1
    # decode: shard the KV cache on batch (default) or leave batch
    # replicated so cache_seq can take both mesh axes (qwen1.5-32b's
    # 40-head MHA cache does not fit otherwise)
    decode_batch_shard: bool = True
    # KV cache storage dtype: "" = model dtype; "int8" = quantized cache
    # with per-(token, head) f32 scales (qwen1.5-32b's 5.1 TiB cache is
    # 20.5 GiB/chip at bf16 — structurally over the 16 GiB budget on 256
    # chips; int8 halves it)
    kv_cache_dtype: str = ""
    # embedding tables padded up to a multiple of this so the vocab dim
    # shards (whisper's 51865 is not 16-divisible); padded logits are
    # masked in unembed
    vocab_pad_to: int = 16

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_to
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(1, self.n_kv_heads)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Width of a q/k head: the rope and no-rope parts under MLA."""
        if self.mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.resolved_head_dim

    @property
    def n_scanned_layers(self) -> int:
        """Layers of the uniform stack, after the leading dense ones."""
        return self.n_layers - self.first_k_dense

    def is_attention_layer(self, i: int) -> bool:
        """Hybrid interleave (Jamba 1:7 -> attn_period=8)."""
        if self.attn_period <= 0:
            return True
        return i % self.attn_period == self.attn_offset

    def is_moe_layer(self, i: int) -> bool:
        return (self.moe is not None and i >= self.first_k_dense
                and (i % self.moe.every == self.moe.every - 1))

    def param_count(self) -> int:
        """Total parameters (embedding included), exact per family."""
        from repro.models.registry import count_params  # lazy: avoids cycle
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.registry import count_params
        return count_params(self, active_only=True)

    def scaled(self, **overrides) -> "ModelConfig":
        """Reduced copy for smoke tests (same family, tiny dims)."""
        return dataclasses.replace(self, **overrides)
