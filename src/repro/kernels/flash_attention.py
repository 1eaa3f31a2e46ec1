"""Flash attention for TPU (Pallas): causal / sliding-window / GQA.

TPU-native adaptation of the flash-attention insight: the (lq × lk)
score matrix never touches HBM.  Blocking is chosen for the TPU memory
hierarchy — (block_q × d) query tiles and (block_k × d) key/value tiles
stream HBM→VMEM, the (block_q × block_k) score tile lives only in VMEM,
and both matmuls hit the MXU with 128-aligned dims.  Online softmax
(running max m, normalizer l, accumulator acc in VMEM scratch) carries
across the innermost grid dimension, which TPU executes sequentially.

Grid: (batch, q_heads, lq/block_q, lk/block_k) — the kv-block axis is
innermost; GQA maps q-head h to kv-head h // (hq // hkv) in the K/V
index_map (no materialized head broadcast).  The wrapper moves heads
ahead of the sequence, (b, l, h, d) -> (b, h, l, d), so every block's
last two dims are (seq block, head_dim): Mosaic tiles the last two
dims in (8, 128) units and cannot squeeze a head axis out of the
second-minor position.  The running max / normalizer scratch is
lane-replicated (block_q, 128) for the same reason.

The kernel is forward-only; ``ops.flash_attention`` wraps it in a
``jax.custom_vjp`` whose backward recomputes through the jnp reference
(a flash backward kernel is future work).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

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
      q_ref (block_q, d), k_ref/v_ref (block_k, d), o_ref (block_q, d)
      m_ref/l_ref (block_q, 128) f32 scratch (lane-replicated),
      acc_ref (block_q, d) f32 scratch
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
    """q (b, lq, hq, d); k/v (b, lk, hkv, d) -> (b, lq, hq, d)."""
    b, lq, hq, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
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
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),       # accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
