"""Architecture registry: one place that maps a ModelConfig to its family's
param defs / forward / loss / serving functions and input specs.

Families:
  dense | moe   -> transformer.py   (llama/qwen/mistral/chameleon/qwen3/deepseek)
  ssm           -> ssm.py           (xLSTM)
  hybrid        -> hybrid.py        (jamba)
  audio         -> encdec.py        (whisper backbone, stub frontend)
  vlm           -> transformer.py   (chameleon: early-fusion VQ tokens = LM)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, ssm, transformer
from repro.models import params as P
from repro.models.config import ModelConfig
from repro.models.layers import cross_entropy
from repro.models.sharding import AxisRules


@dataclasses.dataclass(frozen=True)
class Family:
    param_defs: Callable[[ModelConfig], Any]
    loss_fn: Callable[..., Tuple[jax.Array, Dict[str, Any]]]
    decode_fn: Optional[Callable[..., Any]] = None
    init_state: Optional[Callable[..., Any]] = None
    state_specs: Optional[Callable[..., Any]] = None


# --------------------------------------------------------------- loss fns
def _lm_loss(cfg, params, batch):
    return transformer.loss_fn(cfg, params, batch)


def _ssm_loss(cfg, params, batch):
    logits, aux = ssm.xlstm_forward(cfg, params, batch["tokens"])
    return cross_entropy(logits, batch["labels"]), {"aux_loss": aux}


def _hybrid_loss(cfg, params, batch):
    logits, aux = hybrid.forward(cfg, params, batch["tokens"])
    nll = cross_entropy(logits, batch["labels"])
    w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return nll + w * aux, {"loss": nll, "aux_loss": aux}


def _encdec_loss(cfg, params, batch):
    logits, aux = encdec.forward(cfg, params, batch)
    return cross_entropy(logits, batch["labels"]), {"aux_loss": aux}


# --------------------------------------------------------------- decode fns
def _lm_decode(cfg, params, token, cache, index):
    return transformer.forward_decode(cfg, params, token, cache, index)


def _lm_init_state(cfg, batch, max_seq):
    return transformer.init_cache(cfg, batch, max_seq)


def _lm_state_specs(cfg, batch, max_seq, rules):
    return transformer.cache_specs(cfg, batch, max_seq, rules)


def _ssm_decode(cfg, params, token, state, index):
    return ssm.xlstm_decode(cfg, params, token, state, index)


def _ssm_init_state(cfg, batch, max_seq):
    return ssm.xlstm_init_state(cfg, batch)


def _ssm_state_specs(cfg, batch, max_seq, rules):
    from jax.sharding import PartitionSpec as PS
    state = jax.eval_shape(lambda: ssm.xlstm_init_state(cfg, batch))

    def leaf_spec(x):
        if rules is None:
            return PS()
        # (layer, batch, heads, [hd, [hd]]): batch over DP axes; the
        # per-head state dim over 'model' where it divides (192/16 ok)
        axes = [None] * x.ndim
        if x.ndim >= 2:
            axes[1] = "batch"
        if x.ndim >= 4:
            axes[3] = "model"
        return rules.spec(axes, x.shape)

    return jax.tree_util.tree_map(leaf_spec, state)


def _hybrid_state_specs(cfg, batch, max_seq, rules):
    return hybrid.state_specs(cfg, batch, max_seq, rules)


def _encdec_decode(cfg, params, token, cache, index):
    return encdec.forward_decode(cfg, params, token, cache, index)


FAMILIES: Dict[str, Family] = {
    "dense": Family(transformer.param_defs, _lm_loss, _lm_decode,
                    _lm_init_state, _lm_state_specs),
    "moe": Family(transformer.param_defs, _lm_loss, _lm_decode,
                  _lm_init_state, _lm_state_specs),
    "vlm": Family(transformer.param_defs, _lm_loss, _lm_decode,
                  _lm_init_state, _lm_state_specs),
    "ssm": Family(ssm.xlstm_param_defs, _ssm_loss, _ssm_decode,
                  _ssm_init_state, _ssm_state_specs),
    "hybrid": Family(hybrid.param_defs, _hybrid_loss, hybrid.forward_decode,
                     hybrid.init_state, _hybrid_state_specs),
    "audio": Family(encdec.param_defs, _encdec_loss, _encdec_decode,
                    encdec.init_cache, encdec.cache_specs),
}


def family(cfg: ModelConfig) -> Family:
    return FAMILIES[cfg.family]


# --------------------------------------------------------------- public API
def param_defs(cfg: ModelConfig) -> Any:
    return family(cfg).param_defs(cfg)


def init_params(cfg: ModelConfig, key: jax.Array) -> Any:
    return P.init_tree(param_defs(cfg), key, cfg.dtype)


def param_specs(cfg: ModelConfig, rules: Optional[AxisRules]) -> Any:
    return P.spec_tree(param_defs(cfg), rules)


def param_sds(cfg: ModelConfig) -> Any:
    return P.sds_tree(param_defs(cfg), cfg.dtype)


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    total = P.count(param_defs(cfg))
    if active_only and cfg.moe is not None:
        # subtract routed-expert params that are not active per token
        m = cfg.moe
        f = m.d_expert or cfg.d_ff
        per_expert = 3 * cfg.d_model * f
        n_moe_layers = sum(1 for i in range(cfg.n_layers)
                           if cfg.is_moe_layer(i))
        if cfg.family == "hybrid":
            period = cfg.attn_period or 1
            n_moe_layers = (cfg.n_layers // period) * sum(
                1 for j in range(period)
                if j % m.every == m.every - 1)
        inactive = n_moe_layers * (m.n_held - m.top_k) * per_expert
        total -= max(0, inactive)
    return total


def loss_fn(cfg: ModelConfig) -> Callable:
    return functools.partial(family(cfg).loss_fn, cfg)


def decode_fn(cfg: ModelConfig) -> Callable:
    return functools.partial(family(cfg).decode_fn, cfg)
