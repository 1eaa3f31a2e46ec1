"""Fused residual-add + RMSNorm (Pallas TPU).

The pre-norm block pattern ``y = x + sublayer(...); h = rms_norm(y)``
makes XLA read the freshly-written sum back from HBM to normalize it.
This kernel fuses the two: one pass streams ``x`` and ``res`` through
VMEM, writes the sum (the next layer's residual stream) AND its
normalized projection — two reads + two writes instead of three reads +
two writes, and the f32 mean-square reduction never leaves VMEM.

SNIPPETS.md's mamba-jax interface lists exactly this op as its open
TODO (``def add_norm(): pass``); this is the filled-in version.

Grid: row blocks over the flattened (rows, d) view, same tiling as
``kernels/rmsnorm.py`` — a (8, d) f32 tile stays comfortably in VMEM
for every model width in the zoo, and a partial last block is padded.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rmsnorm import pad_rows


def _residual_rmsnorm_kernel(x_ref, r_ref, w_ref, s_ref, o_ref, *,
                             eps: float):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    s_ref[...] = s.astype(s_ref.dtype)
    o_ref[...] = (s * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def residual_rmsnorm(x: jax.Array, res: jax.Array, weight: jax.Array, *,
                     eps: float = 1e-6, block_rows: int = 8,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """x/res (..., d), weight (d,) -> (sum, rms_norm(sum) * weight).

    ``sum`` (= x + res) is the residual stream the next sublayer adds
    onto; the normalized output feeds the current sublayer.  Both carry
    x's dtype.
    """
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    block_rows = min(block_rows, rows)
    x2 = pad_rows(x.reshape(rows, d), block_rows)
    r2 = pad_rows(res.reshape(rows, d), block_rows)
    grid = (x2.shape[0] // block_rows,)

    summed, normed = pl.pallas_call(
        functools.partial(_residual_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
        ],
        interpret=interpret,
        name="residual_rmsnorm",
    )(x2, r2, weight)
    return (summed[:rows].reshape(orig_shape),
            normed[:rows].reshape(orig_shape))
