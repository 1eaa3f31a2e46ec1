"""Fused DSSP delayed-gradient apply (Pallas TPU).

The optimizer update is the op DSSP itself makes hot: every step reads
the delayed gradient out of the ring buffer, folds it into momentum and
applies it (arithmetic intensity ~0.25 flop/byte — purely HBM-bound).
Unfused XLA issues separate read/write passes for the momentum update
and the parameter update; this kernel streams p, m, g through VMEM once:

    m' = beta * m + scale * g        (scale = staleness damping * warm-up
    p' = p - lr * m'                  validity from the DSSP pipeline)

4 HBM transfers per element (read p, m, g; write p', m' aliased over p,
m) instead of 6 — a 1.5x traffic cut on the dominant term of the update
phase.  ``scale`` and ``lr`` arrive in SMEM as scalar-prefetch-style
(1, 1) operands so the controller can re-tune them without recompiling.

Tiles: (8, 512) f32 — lane-dim multiple of 128, 16 KiB per operand tile.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.perfcount import WIRE
from repro.wireformat import WIRE_LANES as _LANES
from repro.wireformat import WIRE_ROWS as _ROWS
from repro.wireformat import pack_flat, resolve_wire_dtype


def _fused_update_kernel(scalars_ref, p_ref, m_ref, g_ref,
                         po_ref, mo_ref, *, beta: float):
    lr = scalars_ref[0, 0]
    scale = scalars_ref[0, 1]
    m = (beta * m_ref[...].astype(jnp.float32)
         + scale * g_ref[...].astype(jnp.float32))
    po_ref[...] = (p_ref[...].astype(jnp.float32)
                   - lr * m).astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)


def fused_update(p: jax.Array, m: jax.Array, g: jax.Array, *,
                 lr, beta: float = 0.9, scale=1.0,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One fused momentum step on an arbitrary-shaped leaf.

    Returns (p', m') with the input dtypes.  lr/scale may be python
    floats or traced scalars (no recompile on change).  The call is one
    jitted program: called eagerly (the server's apply), each reshape
    and pad would otherwise be its own dispatch with its own full-size
    copy in device memory.
    """
    WIRE.pallas_calls += 1
    return _fused_update(p, m, g, lr, scale, beta=beta, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def _fused_update(p, m, g, lr, scale, *, beta: float, interpret: bool):
    orig_shape = p.shape
    n = p.size
    tile = _ROWS * _LANES
    pad = (-n) % tile
    if pad:
        p2 = jnp.pad(p.reshape(-1), (0, pad))
        m2 = jnp.pad(m.reshape(-1), (0, pad))
        g2 = jnp.pad(g.reshape(-1), (0, pad))
    else:
        p2, m2, g2 = p.reshape(-1), m.reshape(-1), g.reshape(-1)
    rows = (n + pad) // _LANES
    p2 = p2.reshape(rows, _LANES)
    m2 = m2.reshape(rows, _LANES)
    g2 = g2.reshape(rows, _LANES)
    scalars = jnp.array([[lr, scale]], jnp.float32)
    grid = (rows // _ROWS,)

    spec = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))
    po, mo = pl.pallas_call(
        functools.partial(_fused_update_kernel, beta=beta),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)), spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), m.dtype)),
        interpret=interpret,
    )(scalars, p2, m2, g2)
    po = po.reshape(-1)[:n].reshape(orig_shape)
    mo = mo.reshape(-1)[:n].reshape(orig_shape)
    return po, mo


# ----------------------------------------------------- contributor batching
# The coalesced server apply: K workers' gradient buffers for the SAME
# parameter region, folded in one launch.  p and m stream through VMEM
# once regardless of K; only the gradient traffic scales with the number
# of contributors — (2 + K) reads + 2 writes per element instead of the
# 3K + 2K a sequence of ``fused_update`` launches costs.
#
# The fold is SEQUENTIAL inside the kernel (contributor 0 first, each
# step rounding p/m to the storage precision exactly as a standalone
# launch's store + reload would), so the result matches K back-to-back
# ``fused_update`` calls in enqueue order — bitwise for f32 state (the
# equivalence tests assert it) and K=1 for every dtype (dispatched to
# the standalone kernel outright); narrow-dtype folds at K > 1 may
# differ by 1 ulp where XLA picks a different FMA contraction around
# the in-register rounding.  Coalescing changes launch count, not
# semantics.  K is static: one compilation per distinct window fill
# (bounded by the coalesce knob).

def _round_to(x: jax.Array, dtype) -> jax.Array:
    """Round an f32 value to ``dtype``'s precision WITHOUT leaving f32.

    An ``astype(dtype).astype(f32)`` round-trip inside one fused
    computation is elided by XLA's excess-precision rule, which would
    make the batched fold drift (by 1 ulp) from K sequential launches
    that physically store the narrow dtype between steps.
    ``lax.reduce_precision`` is the documented non-elidable rounding,
    but Mosaic has no lowering for it, so bf16 (the wire dtype) rounds
    to nearest-even on the f32 bit pattern instead — the same result,
    from integer ops both compilers keep.
    """
    if jnp.dtype(dtype) == jnp.float32:
        return x
    if jnp.dtype(dtype) == jnp.bfloat16:
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        lsb = (bits >> jnp.uint32(16)) & jnp.uint32(1)
        bits = (bits + jnp.uint32(0x7FFF) + lsb) & jnp.uint32(0xFFFF0000)
        return jnp.where(jnp.isnan(x), x,
                         jax.lax.bitcast_convert_type(bits, jnp.float32))
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def _fused_update_batched_kernel(scalars_ref, p_ref, m_ref, g_ref,
                                 po_ref, mo_ref, *, beta: float, k: int):
    lr = scalars_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    for j in range(k):          # unrolled at trace time (k is static)
        scale = scalars_ref[0, 1 + j]
        # A standalone launch updates p with the UNROUNDED f32 momentum
        # and narrows only at the store; the carried values round like a
        # store + reload.  Mirror that exactly.
        mf = beta * m + scale * g_ref[j].astype(jnp.float32)
        p = _round_to(p - lr * mf, po_ref.dtype)
        m = _round_to(mf, mo_ref.dtype)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)


def fused_update_batched(p: jax.Array, m: jax.Array, gs: jax.Array, *,
                         lr, beta: float = 0.9, scales=None,
                         interpret: bool = False,
                         ) -> Tuple[jax.Array, jax.Array]:
    """One fused momentum fold of K stacked gradients into (p, m).

    ``gs`` has shape ``(K,) + p.shape`` (one stacked buffer per
    contributor); ``scales`` is a length-K sequence of per-contributor
    step scales (staleness damping), python floats or traced scalars.
    Returns (p', m') with the input dtypes, bitwise-identical to K
    sequential ``fused_update(p, m, gs[j], scale=scales[j])`` calls.
    """
    k = gs.shape[0]
    if gs.shape[1:] != p.shape:
        raise ValueError(f"stacked grads {gs.shape} do not match "
                         f"parameter shape {p.shape}")
    if scales is None:
        scales = (1.0,) * k
    if len(scales) != k:
        raise ValueError(f"{len(scales)} scales for {k} stacked grads")
    if k == 1:
        # The standalone kernel IS the K=1 fold — dispatching to it
        # makes a window of one bitwise-trivially identical to the
        # uncoalesced path for every dtype.
        return fused_update(p, m, gs[0], lr=lr, beta=beta,
                            scale=scales[0], interpret=interpret)
    WIRE.pallas_calls += 1
    return _fused_update_batched(p, m, gs, lr, tuple(scales), beta=beta,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def _fused_update_batched(p, m, gs, lr, scales, *, beta: float,
                          interpret: bool):
    k = gs.shape[0]
    orig_shape = p.shape
    n = p.size
    tile = _ROWS * _LANES
    pad = (-n) % tile
    if pad:
        p2 = jnp.pad(p.reshape(-1), (0, pad))
        m2 = jnp.pad(m.reshape(-1), (0, pad))
        g2 = jnp.pad(gs.reshape(k, -1), ((0, 0), (0, pad)))
    else:
        p2, m2, g2 = p.reshape(-1), m.reshape(-1), gs.reshape(k, -1)
    rows = (n + pad) // _LANES
    p2 = p2.reshape(rows, _LANES)
    m2 = m2.reshape(rows, _LANES)
    g2 = g2.reshape(k, rows, _LANES)
    scalars = jnp.stack(
        [jnp.asarray(lr, jnp.float32)]
        + [jnp.asarray(s, jnp.float32) for s in scales]).reshape(1, 1 + k)
    grid = (rows // _ROWS,)

    spec = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))
    gspec = pl.BlockSpec((k, _ROWS, _LANES), lambda i: (0, i, 0))
    po, mo = pl.pallas_call(
        functools.partial(_fused_update_batched_kernel, beta=beta, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1 + k), lambda i: (0, 0)),
                  spec, spec, gspec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), m.dtype)),
        interpret=interpret,
        name="fused_momentum_apply_batched",
    )(scalars, p2, m2, g2)
    po = po.reshape(-1)[:n].reshape(orig_shape)
    mo = mo.reshape(-1)[:n].reshape(orig_shape)
    return po, mo


# ---------------------------------------------------------- shard batching
# A parameter-server shard holds many small leaves (slices of the model's
# pytree).  Calling ``fused_update`` per leaf issues one ``pallas_call``
# per leaf — grid-launch overhead dominates for the tail of small tensors.
# Instead the shard's leaves are packed once into a single (rows, 512)
# buffer and the WHOLE shard updates in one kernel launch; momentum can
# stay resident in the packed layout between steps (see
# ``repro.ps.sharded.server``).

def pack_shard(leaves: Sequence[jax.Array], dtype=None) -> jax.Array:
    """Flatten + concatenate leaves into one lane-aligned (rows, 512) buffer.

    ``dtype=None`` (default) preserves a uniform leaf dtype on the wire
    — bf16 leaves pack into a bf16 buffer and round-trip bitwise through
    ``unpack_shard`` instead of silently bouncing through f32 (which
    would also flip the fused apply's *persistent* accumulation dtype to
    f32 while the tree path accumulates in the leaf dtype).  Mixed-dtype
    leaf lists are explicitly promoted to f32; pass ``dtype=`` to force
    a wire dtype.
    """
    if not leaves:
        return jnp.zeros((0, _LANES), dtype or jnp.float32)
    if dtype is None:
        dtype = resolve_wire_dtype((jnp.dtype(x.dtype) for x in leaves),
                                   default=jnp.dtype(jnp.float32))
    return pack_flat(leaves, dtype)


def unpack_shard(buf: jax.Array, shapes: Sequence[Tuple[int, ...]],
                 dtypes: Sequence) -> List[jax.Array]:
    """Inverse of ``pack_shard`` given the original leaf shapes/dtypes.

    Casts only when the buffer dtype differs from a leaf's dtype (a
    uniform-dtype shard never round-trips through another precision).
    """
    WIRE.unpacks += 1
    flat = buf.reshape(-1)
    out: List[jax.Array] = []
    off = 0
    for shape, dt in zip(shapes, dtypes):
        size = math.prod(shape) if shape else 1
        out.append(flat[off:off + size].reshape(shape).astype(dt))
        off += size
    return out


def fused_update_shard(ps: Sequence[jax.Array], ms: Sequence[jax.Array],
                       gs: Sequence[jax.Array], *, lr, beta: float = 0.9,
                       scale=1.0, interpret: bool = False,
                       ) -> Tuple[List[jax.Array], List[jax.Array]]:
    """One fused momentum step over a whole shard's leaf list.

    Packs (p, m, g) into three (rows, 512) buffers, runs a single
    ``pallas_call`` over the concatenation, and unpacks back to the input
    shapes/dtypes.  Numerically identical to per-leaf ``fused_update``
    (the kernel is elementwise).
    """
    if len(ps) != len(ms) or len(ps) != len(gs):
        raise ValueError("p/m/g leaf lists must align")
    if not ps:
        return [], []
    shapes = [p.shape for p in ps]
    p_dtypes = [p.dtype for p in ps]
    m_dtypes = [m.dtype for m in ms]
    po, mo = fused_update(pack_shard(ps), pack_shard(ms), pack_shard(gs),
                          lr=lr, beta=beta, scale=scale,
                          interpret=interpret)
    return (unpack_shard(po, shapes, p_dtypes),
            unpack_shard(mo, shapes, m_dtypes))
