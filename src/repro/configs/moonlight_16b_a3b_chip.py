"""Moonlight-16B-A3B, one TPU v5e chip's share: every published width,
the leading dense layer and 4 of the 26 expert layers, 8 of the 64
routed experts of each, 20480 of the 163840 vocabulary rows.

Source: moonshotai/Moonlight-16B-A3B (its hf config.json; DeepSeek-V3's
layout) — see ``moonlight_16b_a3b``.

The deployment this is the share of: eight chips share each layer by
expert parallelism.  Each holds 8 routed experts of every expert layer
and one eighth of the vocabulary, and holds attention, the router (all
64 outputs), the 2 shared experts and the dense layer whole; the 22
expert layers left out are further pipeline stages.  Data-parallel
workers each hold such a share behind the sharded parameter server.

Reduced: ``n_layers`` 27 -> 5 (the dense layer + 4 expert layers),
``moe.held`` 64 -> 8 (experts 0-7; the router still scores 64), and
``vocab_size`` 163840 -> 20480.  568,484,608 parameters: the dense
layer 82,973,184, an expert layer 100,405,824, the vocabulary slice
83,886,080, the final norm 2048.  Nothing is exchanged on one chip: a
layer's output is the part its held experts give plus the shared
experts, the rest of the routed output being another chip's.

Assumed: the router bias (``e_score_correction_bias``) starts from
N(0, 1/64), not zeros, so that it picks experts from the first step;
DeepSeek-V3's sequence-wise balance loss and its load-driven update of
that bias are left out (the bias does not train).
"""

import dataclasses

from repro.configs.moonlight_16b_a3b import CONFIG as _FULL
from repro.configs.moonlight_16b_a3b import SMOKE, SMOKE_MOE

CONFIG = _FULL.scaled(name="moonlight-16b-a3b-chip", n_layers=5,
                      vocab_size=20480,
                      moe=dataclasses.replace(_FULL.moe, held=8,
                                              held_start=0))


def smoke_config():
    """The full smoke layout holding experts 4-7 of 16."""
    return CONFIG.scaled(moe=dataclasses.replace(
        CONFIG.moe, held=4, held_start=4, **SMOKE_MOE), **SMOKE)
