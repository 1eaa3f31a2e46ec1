"""Selective-scan (Mamba S6) recurrence as a Pallas TPU kernel.

    h_t = exp(delta_t * A) * h_{t-1} + delta_t * B_t * u_t
    y_t = C_t . h_t

The XLA formulations materialize the discretized (l, d_inner, d_state)
Abar/Bbar tensors in HBM before scanning them; this kernel streams one
chunk of (u, delta, B, C) into VMEM, discretizes per-timestep on the
fly, and carries the hidden state in a VMEM scratch across chunk steps
— the state tensor never round-trips HBM and the per-step working set
is O(d_inner * d_state) instead of O(l * d_inner * d_state).

Grid: (batch, d_inner / block_d, l / chunk) with the chunk axis
innermost — TPU executes it sequentially, which is exactly the
dependence order of the scan (and interpret mode preserves the same
order on CPU).  Channels of d_inner never interact, so they are blocked
(512 lanes where d_inner allows): at Jamba's d_inner 8192 an unblocked
(chunk, d_inner) tile set would overflow the 16 MiB of scoped VMEM.

Layout for Mosaic: the state is held transposed, (d_state, d_inner),
so d_inner sits on the lanes and a timestep's (1, d_inner) row of u and
delta broadcasts over sublanes without a relayout.  Time is read and
written in 8-row slabs at offsets the compiler can prove 8-aligned and
unrolled within the slab; the (1, d_state) rows of B and C become
(d_state, 1) columns by a masked lane reduction.  The sequence is padded
to a whole number of slabs with delta = 0, which leaves the state
unchanged (exp(0) = 1, zero input), and the padded outputs are dropped.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SLAB = 8   # sublane tile: rows read / written per aligned access
_BLOCK_D = (512, 256, 128)   # d_inner lane blocks, largest first


def _column(row: jax.Array) -> jax.Array:
    """(1, n) -> (n, 1) by a masked lane reduction (no transpose)."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _ssm_scan_kernel(u_ref, delta_ref, at_ref, b_ref, c_ref, h0_ref,
                     y_ref, hlast_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    at = at_ref[...].astype(jnp.float32)                # (ds, bd)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SLAB, u_ref.shape[1]), 0)

    def slab(s, h):
        base = pl.multiple_of(s * _SLAB, _SLAB)
        rows = pl.ds(base, _SLAB)
        u = u_ref[rows, :].astype(jnp.float32)           # (8, bd)
        d = delta_ref[rows, :].astype(jnp.float32)       # (8, bd)
        bm = b_ref[rows, :].astype(jnp.float32)          # (8, ds)
        cm = c_ref[rows, :].astype(jnp.float32)          # (8, ds)
        y = jnp.zeros(u.shape, jnp.float32)
        for j in range(_SLAB):
            dt = d[j:j + 1, :]                           # (1, bd)
            h = (jnp.exp(at * dt) * h
                 + _column(bm[j:j + 1, :]) * (dt * u[j:j + 1, :]))
            yt = jnp.sum(h * _column(cm[j:j + 1, :]), axis=0,
                         keepdims=True)                  # (1, bd)
            y = jnp.where(row == j, yt, y)
        y_ref[rows, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // _SLAB, slab, h_ref[...])
    h_ref[...] = h
    hlast_ref[...] = h.astype(hlast_ref.dtype)


def ssm_scan(u: jax.Array, delta: jax.Array, a: jax.Array,
             bmat: jax.Array, cmat: jax.Array, h0: jax.Array, *,
             chunk: int = 128, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """u/delta (b, l, di); a (di, ds); bmat/cmat (b, l, ds);
    h0 (b, di, ds) -> (y (b, l, di) in u's dtype, h_last (b, di, ds) f32).
    """
    b, l, di = u.shape
    ds = a.shape[-1]
    chunk = min(chunk, l) if chunk > 0 else l
    if l % chunk:
        chunk = l
    chunk = -(-chunk // _SLAB) * _SLAB          # whole slabs per chunk
    pad = (-l) % chunk
    if pad:
        # delta = 0 on the padded steps keeps the state where it was
        u, delta, bmat, cmat = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                                for x in (u, delta, bmat, cmat))
    lp = l + pad
    bd = next((n for n in _BLOCK_D if di % n == 0), di)
    grid = (b, di // bd, lp // chunk)

    y, h_last = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((None, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((ds, bd), lambda bi, di_, ci: (0, di_)),
            pl.BlockSpec((None, chunk, ds), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((None, chunk, ds), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((None, ds, bd), lambda bi, di_, ci: (bi, 0, di_)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((None, ds, bd), lambda bi, di_, ci: (bi, 0, di_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, lp, di), u.dtype),
            jax.ShapeDtypeStruct((b, ds, di), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((ds, bd), jnp.float32),   # carried state, transposed
        ],
        interpret=interpret,
        name="ssm_scan",
    )(u, delta, a.T, bmat, cmat, h0.transpose(0, 2, 1))
    return y[:, :l], h_last.transpose(0, 2, 1)
