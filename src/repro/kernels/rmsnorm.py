"""Fused RMSNorm (Pallas TPU).

Bandwidth-bound op: unfused XLA does read-x (square+mean), read-x again
(scale), write — the kernel does one HBM read of x, one write, with the
f32 reduction and the weight multiply fused in VMEM.

Grid: row blocks; BlockSpec tiles (block_rows, d) — d up to 12288 keeps
a (8, 12288) f32 tile at 393 KiB, comfortably inside VMEM.  Rows that do
not fill the last block are zero-padded (Mosaic refuses a row block
that is neither a multiple of 8 nor the whole dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 8, interpret: bool = False) -> jax.Array:
    """x (..., d), weight (d,) -> same shape/dtype as x."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    # Mosaic takes a row block that is a multiple of 8 or the whole dim:
    # pad the rows up to the block instead of shrinking the block
    x2 = pad_rows(x2, block_rows)
    grid = (x2.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, weight)
    return out[:rows].reshape(orig_shape)


def pad_rows(x2: jax.Array, block_rows: int) -> jax.Array:
    """Zero-pad a (rows, d) array to a whole number of row blocks (the
    padded rows normalize to zeros and are sliced off by the caller)."""
    pad = (-x2.shape[0]) % block_rows
    return jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2
