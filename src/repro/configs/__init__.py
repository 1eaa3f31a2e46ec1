"""Assigned architecture configs (public-literature shapes) + paper's own.

Each module exposes ``CONFIG`` (full-scale) and ``smoke_config()``
(reduced, same family — used by the per-arch smoke tests).  The dry-run
exercises the full configs via ShapeDtypeStruct only.
"""

from repro.configs.shapes import SHAPES, ShapeSpec, input_specs
from repro.models.config import ModelConfig

_ARCH_MODULES = (
    "h2o_danube_1p8b",
    "h2o_danube_1p8b_chip",
    "qwen15_110b",
    "qwen15_32b",
    "mistral_large_123b",
    "qwen3_moe_235b_a22b",
    "deepseek_moe_16b",
    "xlstm_125m",
    "whisper_tiny",
    "chameleon_34b",
    "jamba_v01_52b",
    "moonlight_16b_a3b",
    "moonlight_16b_a3b_chip",
)

# CLI ids use dashes (match the assignment listing)
_ALIASES = {
    "h2o-danube-1.8b": "h2o_danube_1p8b",
    "h2o-danube-1.8b-chip": "h2o_danube_1p8b_chip",
    "qwen1.5-110b": "qwen15_110b",
    "qwen1.5-32b": "qwen15_32b",
    "mistral-large-123b": "mistral_large_123b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "xlstm-125m": "xlstm_125m",
    "whisper-tiny": "whisper_tiny",
    "chameleon-34b": "chameleon_34b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "moonlight-16b-a3b-chip": "moonlight_16b_a3b_chip",
}


def arch_names():
    return list(_ALIASES)


def get_config(name: str) -> ModelConfig:
    import importlib
    mod = _ALIASES.get(name, name).replace("-", "_").replace(".", "p")
    try:
        m = importlib.import_module(f"repro.configs.{mod}")
    except ImportError as e:
        raise KeyError(f"unknown architecture {name!r}: {e}") from e
    return m.CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    import importlib
    mod = _ALIASES.get(name, name).replace("-", "_").replace(".", "p")
    m = importlib.import_module(f"repro.configs.{mod}")
    return m.smoke_config()
