"""h2o-danube-1.8b, one TPU v5e chip's share: the published widths at
8 of the 24 layers.

Source: h2o-danube-1.8b (arXiv:2401.16818; its hf config.json) —
d_model 2560, 32 query heads over 8 KV heads (head_dim 80), SwiGLU
d_ff 6912, vocab 32000, sliding window 4096, rope theta 10000.

Reduced: ``n_layers`` 24 -> 8, nothing else.  Every width, the head
layout, the window and the vocabulary are as published.  The
parameter-server path keeps several parameter-sized buffers on the one
chip at once — the server's packed params and momentum, the apply's
outputs, one gradient wire per worker, and the unpacked parameters and
activations inside each worker step — so the 3.7 GB bf16 copy of the
24-layer model does not fit 16 GB, while 8 layers (719.6 M parameters,
1.44 GB bf16) does, with the worker step at batch 4 x seq 2048.  The
layers left out would sit on further chips as pipeline stages, so this
is the share of a three-stage deployment that one stage holds; a layer
is not split across chips (the model is small enough that the
published deployment is data parallel, ``model_axis_role='dp'``).

Assumed: nothing beyond the parent config.  Fewer layers make the
host's work and the embedding/unembedding a larger share of a step
than in the full model.
"""

from repro.configs.h2o_danube_1p8b import CONFIG as _FULL
from repro.configs.h2o_danube_1p8b import smoke_config  # noqa: F401

CONFIG = _FULL.scaled(name="h2o-danube-1.8b-chip", n_layers=8)
