"""Fused wire-compression kernels over the packed (rows, 512) buffer.

The tree-path compressors (``repro.optim.compression``) run one XLA
dispatch chain *per pytree leaf*: quantize, dequantize and the
error-feedback update each read/write the leaf separately, and the tail
of small leaves pays one dispatch each.  On the packed wire format the
whole shard is a single lane-aligned buffer, so the entire
compress-decode-error-feedback pipeline fuses into ONE Pallas pass
through VMEM per shard:

    read  g (wire dtype), e (f32)          2 transfers
    write g' (decoded),  e' (f32)          2 transfers

vs. the unfused chain's 6+ (read g,e; write q; read q; write g'; write
e') — and one kernel launch per *shard* instead of one dispatch chain
per *leaf*.

Scale granularity: one scale per (8, 512) grid tile instead of the tree
path's one per tensor.  Per-tile scaling is *finer* (4096 elements share
a scale — strictly better quantization error than per-tensor on large
leaves) and is what keeps the kernel single-pass: a per-shard scale
would need a global max reduction before quantizing (two passes).  The
trade is visible only in the tests' tolerance, not in the API.

Both kernels emit the DECODED gradient (like the tree compressors): the
convergence-relevant information loss is what the experiments study;
the wire-byte reduction is priced by ``wire_bytes_per_value`` in the
roofline accounting.

``repro.kernels.ref`` holds the pure-jnp oracles
(``fused_int8_ef_ref`` / ``fused_topk_ef_ref``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.perfcount import WIRE
from repro.wireformat import WIRE_LANES as _LANES
from repro.wireformat import WIRE_ROWS as _ROWS

#: Bisection steps for the top-k threshold search.  24 halvings of the
#: [0, max|g|] interval pin the threshold to ~6e-8 of the dynamic range
#: — indistinguishable from the exact k-th order statistic for f32.
_TOPK_BISECT_ITERS = 24


def _check_wire(buf: jax.Array, err: jax.Array) -> None:
    if buf.ndim != 2 or buf.shape[1] != _LANES or buf.shape[0] % _ROWS:
        raise ValueError(
            f"expected an 8-row-aligned (rows, {_LANES}) wire buffer, "
            f"got {buf.shape}")
    if err.shape != buf.shape:
        raise ValueError(f"error state {err.shape} != buffer {buf.shape}")


# ------------------------------------------------------------------ int8
def _int8_ef_kernel(g_ref, e_ref, dq_ref, er_ref):
    gf = g_ref[...].astype(jnp.float32) + e_ref[...]
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127.0, 127.0)
    dq = q * scale
    dq_ref[...] = dq.astype(dq_ref.dtype)
    er_ref[...] = gf - dq


def fused_int8_ef(g: jax.Array, err: jax.Array, *,
                  interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """int8 quantize + dequant + error feedback, one pass over the wire.

    ``g`` is a packed (rows, 512) gradient buffer (rows % 8 == 0),
    ``err`` the carried f32 error state of the same shape.  Returns
    (decoded gradient in ``g.dtype``, new error state).
    """
    _check_wire(g, err)
    rows = g.shape[0]
    if rows == 0:
        return g, err
    WIRE.pallas_calls += 1
    spec = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _int8_ef_kernel,
        grid=(rows // _ROWS,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANES), g.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)),
        interpret=interpret,
        name="fused_int8_ef",
    )(g, err)


# ------------------------------------------------------------------ top-k
def _topk_ef_kernel(g_ref, e_ref, dq_ref, er_ref, *, fraction: float):
    gf = g_ref[...].astype(jnp.float32) + e_ref[...]
    mag = jnp.abs(gf)
    target = jnp.float32(fraction * mag.size)
    # Threshold = ~k-th largest magnitude, found by bisecting the count
    # curve c(t) = |{x : |x| >= t}| (monotone in t).  A sort/top_k inside
    # the kernel would break the single-VMEM-pass property; the bisection
    # is pure elementwise-compare + reduce, unrolled at trace time.
    lo = jnp.float32(0.0)
    hi = jnp.max(mag) + jnp.float32(1e-12)
    for _ in range(_TOPK_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        keep_mid = jnp.sum((mag >= mid).astype(jnp.float32))
        take = keep_mid >= target
        lo = jnp.where(take, mid, lo)
        hi = jnp.where(take, hi, mid)
    kept = jnp.where(mag >= lo, gf, 0.0)
    dq_ref[...] = kept.astype(dq_ref.dtype)
    er_ref[...] = gf - kept


def fused_topk_ef(g: jax.Array, err: jax.Array, *, fraction: float = 0.05,
                  interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Magnitude top-k sparsification + error feedback on the wire buffer.

    Keeps ~``fraction`` of each (8, 512) tile (>= fraction, ties kept),
    zeroes the rest, carries the sparsification residual in ``err``.
    """
    if not 0.0 < fraction <= 1.0:
        raise ValueError("fraction in (0, 1]")
    _check_wire(g, err)
    rows = g.shape[0]
    if rows == 0:
        return g, err
    WIRE.pallas_calls += 1
    spec = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_topk_ef_kernel, fraction=fraction),
        grid=(rows // _ROWS,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANES), g.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)),
        interpret=interpret,
        name="fused_topk_ef",
    )(g, err)
