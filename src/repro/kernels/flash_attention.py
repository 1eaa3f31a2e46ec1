"""Flash attention for TPU (Pallas): causal / sliding-window / GQA.

TPU-native adaptation of the flash-attention insight: the (lq × lk)
score matrix never touches HBM.  Blocking is chosen for the TPU memory
hierarchy — (block_q × d) query tiles and (block_k × d) key/value tiles
stream HBM→VMEM, the (block_q × block_k) score tile lives only in VMEM,
and both matmuls hit the MXU with 128-aligned dims.  Online softmax
(running max m, normalizer l, accumulator acc in VMEM scratch) carries
across the innermost grid dimension, which TPU executes sequentially.

q and k share one head width and v may have another (multi-head latent
attention attends with q/k of 192 and v of 128): the output and its
gradient take v's width.

Grid: (batch, q_heads, lq/block_q, lk/block_k) — the kv-block axis is
innermost; GQA maps q-head h to kv-head h // (hq // hkv) in the K/V
index_map (no materialized head broadcast).  The wrapper moves heads
ahead of the sequence, (b, l, h, d) -> (b, h, l, d), so every block's
last two dims are (seq block, head_dim): Mosaic tiles the last two
dims in (8, 128) units and cannot squeeze a head axis out of the
second-minor position.  The running max / normalizer scratch is
lane-replicated (block_q, 128) for the same reason.

Backward (``flash_attention_bwd``): two kernels that never write an
(lq × lk) array to HBM.  ``flash_attention_stats`` sweeps QK^T once for
each row's log-sum-exp and computes delta = rowsum(dO ∘ o);
``flash_attention_bwd`` recomputes P = exp(S - lse) block by block and
accumulates dV += PᵀdO, dS = P ∘ (dP - delta), dK += dSᵀQ, dQ += dSK.
Its grid is (batch, kv_heads, lk/block_k, group, lq/block_q): dK/dV
for a k block accumulate in VMEM over the group's query heads and the
q blocks, and dQ over the k blocks in a VMEM-resident (group, lq, d)
float32 slab written once per KV head, so GQA needs no head broadcast.
Tiles are held transposed, (block_k, block_q), so the row statistics
are (1, block_q) rows.  Blocks (up to 512) come from the shapes and a
VMEM budget (``bwd_blocks``); fully masked blocks are skipped without
their DMA by clamping the q (or k) index map to the attended range.
Matmul operands stay in the inputs' dtype with float32 accumulation;
S, P, lse, delta and dS are float32, P and dS cast only as matmul
operands.  ``registry.attention_bwd`` picks this backward or the jnp
oracle's vjp from the shapes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128    # lane width of the m / l scratch (values replicated)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int,
                  causal: bool, window: Optional[int],
                  lq: int, lk: int):
    """One (q-block, k-block) step of online-softmax attention.

    Refs (VMEM):
      q_ref (block_q, d), k_ref (block_k, d), v_ref (block_k, dv),
      o_ref (block_q, dv), m_ref/l_ref (block_q, 128) f32 scratch
      (lane-replicated), acc_ref (block_q, dv) f32 scratch
    """
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (queries aligned at the end: pos = lk - lq + i)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (lk - lq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # skip fully-masked blocks (causal: k entirely in the future;
    # window: k entirely before the window)
    block_needed = True
    if causal:
        block_needed = (ki * block_k) <= (qi * block_q + block_q - 1
                                          + (lk - lq))
    if window is not None:
        first_valid = qi * block_q + (lk - lq) - window + 1
        block_needed = jnp.logical_and(
            block_needed, (ki * block_k + block_k - 1) >= first_valid)

    @pl.when(block_needed)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q/k (b, lq|lk, hq|hkv, d); v (b, lk, hkv, dv) -> (b, lq, hq, dv)."""
    b, lq, hq, d = q.shape
    lk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    if hq % hkv:
        raise ValueError("hq must be a multiple of hkv")
    group = hq // hkv
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(f"seq lens ({lq},{lk}) must divide blocks "
                         f"({block_q},{block_k})")
    grid = (b, hq, lq // block_q, lk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), block_q=block_q,
        block_k=block_k, causal=causal, window=window, lq=lq, lk=lk)

    # heads ahead of the sequence: blocks tile (seq block, head_dim)
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((None, None, block_k, dv),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dv),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, dv), jnp.float32),      # accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)


# ================================================================ backward
#: backward block candidates, largest first.  The q block is the lane
#: axis of the backward's (block_k, block_q) tiles and row statistics,
#: so it is a multiple of 128 or the whole of lq (likewise block_k).
_BWD_BLOCKS = (512, 256, 128)
#: VMEM the backward may plan for (v5e and v6e hold 128 MiB a core).
_BWD_VMEM_BUDGET = 64 * 1024 * 1024


def _bwd_block(n: int) -> Optional[int]:
    for blk in _BWD_BLOCKS:
        if n % blk == 0:
            return blk
    return n if n <= _BWD_BLOCKS[0] and n % 8 == 0 else None


def _bwd_vmem_bytes(block_q: int, block_k: int, lq: int, group: int,
                    d: int, itemsize: int, dv: Optional[int] = None) -> int:
    """VMEM of the fused backward: double-buffered blocks (q, k of width
    d; v, dO of width dv), the group's (lq, d) dq slab (float32
    accumulator and its output), and the (block_k, block_q) float32
    tiles in flight."""
    dl = -(-d // _LANES) * _LANES
    dvl = dl if dv is None else -(-dv // _LANES) * _LANES
    ins = (2 * (block_q + block_k) * (dl + dvl) * itemsize
           + 4 * 8 * block_q * 4)
    outs = 2 * (group * lq * dl + block_k * (dl + dvl)) * itemsize
    scratch = group * lq * dl * 4 + block_k * (dl + dvl) * 4
    return ins + outs + scratch + 6 * block_q * block_k * 4


def bwd_blocks(q_shape, k_shape, dtype,
               dv: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) of ``flash_attention_bwd`` for these shapes (v
    ``dv`` wide, default q's width), or None where it does not cover
    them: a length no backward block tiles, or a group's (lq, d) dq
    slab past the VMEM budget."""
    _, lq, hq, d = q_shape
    lk, hkv = k_shape[1], k_shape[2]
    bq, bk = _bwd_block(lq), _bwd_block(lk)
    if bq is None or bk is None or hq % hkv:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    if _bwd_vmem_bytes(bq, bk, lq, hq // hkv, d, itemsize,
                       dv) > _BWD_VMEM_BUDGET:
        return None
    return bq, bk


def _spans(qi, ki, *, block_q, block_k, offset, causal, window):
    """(needed, masked) of one (q-block, k-block) pair: whether any of
    its pairs is attended, and whether some pair in it is not."""
    q_first = qi * block_q + offset
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    needed, whole = True, True
    if causal:
        needed = k_first <= q_last
        whole = k_last <= q_first
    if window is not None:
        needed = jnp.logical_and(needed, k_last > q_first - window)
        whole = jnp.logical_and(whole, k_first > q_last - window)
    return needed, jnp.logical_not(whole)


def _mask_t(s, qi, ki, *, block_q, block_k, offset, causal, window):
    """``s`` (block_k, block_q) with the pairs not attended set to
    NEG_INF, and the mask."""
    shape = s.shape
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = qi * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return jnp.where(mask, s, NEG_INF), mask


def _dot_nt(a, b):
    """a (m, d) . b (n, d)^T -> (m, n) float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _stats_kernel(q_ref, k_ref, o_ref, do_ref, lse_ref, delta_ref,
                  m_ref, l_ref, *, scale: float, block_q: int,
                  block_k: int, offset: int, causal: bool,
                  window: Optional[int]):
    """Row statistics of one q block, over the k blocks (innermost):
    ``lse`` (log-sum-exp of the scaled scores) and ``delta`` =
    rowsum(dO * o), both float32 rows (1, block_q).  Scores are held
    transposed, (block_k, block_q), so a row statistic is a reduction
    over sublanes and lands as a row."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    geom = dict(block_q=block_q, block_k=block_k, offset=offset,
                causal=causal, window=window)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        prod = (o_ref[...].astype(jnp.float32)
                * do_ref[...].astype(jnp.float32))
        col = jnp.sum(prod, axis=1, keepdims=True)            # (bq, 1)
        delta_ref[...] = jnp.broadcast_to(col, (block_q, _LANES)).T[:1]

    def update(masked: bool):
        s = _dot_nt(k_ref[...], q_ref[...]) * scale           # (bk, bq)
        if masked:
            s, mask = _mask_t(s, qi, ki, **geom)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                      + jnp.sum(p, axis=0, keepdims=True))
        m_ref[...] = m_new

    needed, masked = _spans(qi, ki, **geom)
    pl.when(jnp.logical_and(needed, masked))(lambda: update(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(masked)))(
        lambda: update(False))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale: float, block_q: int, block_k: int, offset: int,
                causal: bool, window: Optional[int]):
    """One (k-block, group head, q-block) step of the flash backward.

    Probabilities are recomputed transposed, ``P^T = exp(K Q^T * scale
    - lse)`` (block_k, block_q), then ``dV += P^T dO``, ``dP^T = V
    dO^T``, ``dS^T = P^T * (dP^T - delta)``, ``dK += dS^T Q`` and ``dQ
    += dS K``.  dK/dV accumulate over the group's heads and q blocks;
    dQ over the k blocks, in the group's VMEM-resident (group, lq, d)
    float32 slab, written once per KV head.
    """
    ki = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)
    last_g = gi == pl.num_programs(3) - 1
    last_q = qi == pl.num_programs(4) - 1
    geom = dict(block_q=block_q, block_k=block_k, offset=offset,
                causal=causal, window=window)

    @pl.when((ki == 0) & (gi == 0) & (qi == 0))
    def _zero_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when((gi == 0) & (qi == 0))
    def _zero_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = _dot_nt(k, q) * scale                             # (bk, bq)
        if masked:
            s, _ = _mask_t(s, qi, ki, **geom)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        ds = (p * (_dot_nt(v, do) - delta_ref[...])).astype(q.dtype)
        dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[gi, rows, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, d)

    needed, masked = _spans(qi, ki, **geom)
    pl.when(jnp.logical_and(needed, masked))(lambda: step(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(masked)))(
        lambda: step(False))

    @pl.when(last_g & last_q)
    def _write_dkv():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((ki == pl.num_programs(2) - 1) & last_g & last_q)
    def _write_dq():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, do: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int, block_k: int,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gradients (dq, dk, dv) of ``flash_attention_fwd``'s output ``o``
    under its cotangent ``do``; shapes and end-aligned positions as the
    forward (v, o and do ``dv`` wide).  Blocks from ``bwd_blocks``.

    Two kernels, neither with a single (b, hq, lq, d) output:
    ``flash_attention_stats`` (grid (b, hq, lq/block_q, lk/block_k))
    makes the row statistics, and ``flash_attention_bwd`` (grid (b,
    hkv, lk/block_k, group, lq/block_q)) the three gradients.  Blocks
    that no pair of attends are skipped without their DMA: the q (or
    k) index map is clamped to the attended range, so a skipped step
    repeats its neighbour's block index.
    """
    b, lq, hq, d = q.shape
    lk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    if lq % block_q or lk % block_k:
        raise ValueError(f"seq lens ({lq},{lk}) must divide blocks "
                         f"({block_q},{block_k})")
    nq, nk = lq // block_q, lk // block_k
    offset = lk - lq
    scale = 1.0 / math.sqrt(d)
    geom = dict(block_q=block_q, block_k=block_k, offset=offset,
                causal=causal, window=window)
    qh, kh, vh, oh, doh = (x.transpose(0, 2, 1, 3) for x in (q, k, v, o, do))

    def k_range(qi):
        """First and last k block that q block ``qi`` attends."""
        lo = (jnp.maximum(qi * block_q + offset - window + 1, 0) // block_k
              if window is not None else 0)
        hi = ((qi * block_q + block_q - 1 + offset) // block_k
              if causal else nk - 1)
        return lo, jnp.minimum(hi, nk - 1)

    def q_range(ki):
        """First and last q block that attends k block ``ki``."""
        lo = (jnp.maximum(ki * block_k - offset, 0) // block_q
              if causal else 0)
        hi = (jnp.maximum(ki * block_k + block_k - 2 - offset + window, 0)
              // block_q if window is not None else nq - 1)
        return lo, jnp.minimum(hi, nq - 1)

    def stats_k(bi, hi, qi, ki):
        return (bi, hi // group, _clamp(ki, *k_range(qi)), 0)

    row = pl.BlockSpec((None, None, block_q, d),
                       lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    vrow = pl.BlockSpec((None, None, block_q, dv),
                        lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    stat = pl.BlockSpec((None, None, 1, block_q),
                        lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    stats_shape = jax.ShapeDtypeStruct((b, hq, 1, lq), jnp.float32)
    lse, delta = pl.pallas_call(
        functools.partial(_stats_kernel, scale=scale, **geom),
        grid=(b, hq, nq, nk),
        in_specs=[row, pl.BlockSpec((None, None, block_k, d), stats_k),
                  vrow, vrow],
        out_specs=[stat, stat],
        out_shape=[stats_shape, stats_shape],
        scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32),
                        pltpu.VMEM((1, block_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_stats",
    )(qh, kh, oh, doh)

    def q_block(bi, hk, ki, gi, qi):
        return (bi, hk * group + gi, _clamp(qi, *q_range(ki)), 0)

    def q_stat(bi, hk, ki, gi, qi):
        return (bi, hk * group + gi, 0, _clamp(qi, *q_range(ki)))

    def kv_block(bi, hk, ki, gi, qi):
        return (bi, hk, ki, 0)

    qspec = pl.BlockSpec((None, None, block_q, d), q_block)
    dospec = pl.BlockSpec((None, None, block_q, dv), q_block)
    kspec = pl.BlockSpec((None, None, block_k, d), kv_block)
    vspec = pl.BlockSpec((None, None, block_k, dv), kv_block)
    sspec = pl.BlockSpec((None, None, 1, block_q), q_stat)
    vmem = _bwd_vmem_bytes(block_q, block_k, lq, group, d,
                           jnp.dtype(q.dtype).itemsize, dv)
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, **geom),
        grid=(b, hkv, nk, group, nq),
        in_specs=[qspec, kspec, vspec, dospec, sspec, sspec],
        out_specs=[
            pl.BlockSpec((None, group, lq, d),
                         lambda bi, hk, ki, gi, qi: (bi, hk, 0, 0)),
            kspec, vspec],
        out_shape=[jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, hkv, lk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hkv, lk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((group, lq, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(32 * 1024 * 1024, vmem + vmem // 2),
                                 _BWD_VMEM_BUDGET)),
        interpret=interpret,
        name="flash_attention_bwd",
    )(qh, kh, vh, doh, lse, delta)
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv_))
