"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition the kernel must reproduce;
tests sweep shapes/dtypes and assert_allclose(kernel, ref).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """q (b, lq, hq, d); k (b, lk, hkv, d); v (b, lk, hkv, dv) ->
    (b, lq, hq, dv); GQA broadcast; f32 softmax, scores over sqrt(d).

    Positions are aligned at the END: query i sits at absolute position
    lk - lq + i (standard for self-attention lq == lk and for decode
    suffix queries).
    """
    b, lq, hq, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("blhd,bmhd->bhlm", qf, kf) / math.sqrt(d)
    qpos = jnp.arange(lq)[:, None] + (lk - lq)
    kpos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhlm,bmhd->blhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rmsnorm_ref(x: jax.Array, weight: jax.Array,
                eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def residual_rmsnorm_ref(x: jax.Array, res: jax.Array, weight: jax.Array,
                         eps: float = 1e-6) -> Tuple[jax.Array, jax.Array]:
    """Oracle for ``residual_rmsnorm.residual_rmsnorm``: the pre-norm
    block glue ``s = x + res; (s, rms_norm(s) * weight)`` with the sum
    and the reduction both in f32, outputs cast back to x's dtype."""
    sf = x.astype(jnp.float32) + res.astype(jnp.float32)
    var = jnp.mean(sf * sf, axis=-1, keepdims=True)
    normed = sf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return sf.astype(x.dtype), normed.astype(x.dtype)


def ssm_scan_ref(u: jax.Array, delta: jax.Array, a: jax.Array,
                 bmat: jax.Array, cmat: jax.Array, h0: jax.Array,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the selective scan (Mamba S6): the literal sequential
    recurrence

        h_t = exp(delta_t * A) * h_{t-1} + delta_t * B_t * u_t
        y_t = C_t . h_t

    u/delta (b, l, di); a (di, ds); bmat/cmat (b, l, ds); h0 (b, di, ds).
    Returns (y (b, l, di) in u's dtype, h_last (b, di, ds) f32).  All
    math in f32 — this is the definition both the Pallas kernel and the
    chunked associative-scan formulation must reproduce.
    """
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = bmat.astype(jnp.float32)
    cf = cmat.astype(jnp.float32)

    def step(h, xs):
        ut, dt, bt, ct = xs                # (b, di), (b, di), (b, ds) x2
        abar = jnp.exp(dt[..., None] * af[None])           # (b, di, ds)
        bbar = dt[..., None] * bt[:, None, :] * ut[..., None]
        h = abar * h + bbar
        y = jnp.einsum("bds,bs->bd", h, ct)
        return h, y

    h_last, ys = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (uf.transpose(1, 0, 2), df.transpose(1, 0, 2),
         bf.transpose(1, 0, 2), cf.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2).astype(u.dtype), h_last


def fused_update_ref(p: jax.Array, m: jax.Array, g: jax.Array, *,
                     lr: float, beta: float,
                     scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """DSSP delayed-gradient apply: one fused momentum-SGD step.

        m' = beta * m + scale * g        (scale = staleness damping /
        p' = p - lr * m'                  warm-up validity gate)

    All math in f32; p'/m' cast back to the input dtypes.
    """
    mf = (beta * m.astype(jnp.float32)
          + scale * g.astype(jnp.float32))
    pf = p.astype(jnp.float32) - lr * mf
    return pf.astype(p.dtype), mf.astype(m.dtype)


def fused_update_batched_ref(p: jax.Array, m: jax.Array, gs: jax.Array, *,
                             lr: float, beta: float,
                             scales=None) -> Tuple[jax.Array, jax.Array]:
    """Oracle for ``fused_update.fused_update_batched``: K stacked
    gradients folded through momentum SEQUENTIALLY (enqueue order), each
    step casting p/m back to the storage dtype exactly like a standalone
    ``fused_update`` launch does.  This makes the batched kernel
    bitwise-identical to K sequential ``fused_update`` calls at every K
    — not merely at K=1 — which is what lets the coalesced server path
    be equivalence-tested against the uncoalesced one.
    """
    k = gs.shape[0]
    if scales is None:
        scales = (1.0,) * k
    for j in range(k):
        p, m = fused_update_ref(p, m, gs[j], lr=lr, beta=beta,
                                scale=scales[j])
    return p, m


def _per_tile(buf: jax.Array, rows: int = 8) -> jax.Array:
    """(R, 512) wire buffer -> (R//rows, rows*512) tile-major view."""
    r, lanes = buf.shape
    return buf.reshape(r // rows, rows * lanes)


def fused_int8_ef_ref(g: jax.Array, e: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for ``fused_compress.fused_int8_ef``.

    Per-(8, 512)-tile symmetric int8 quantize/dequant with error
    feedback: gf = g + e; scale = max|gf| / 127 per tile;
    g' = dequant(round(gf/scale)); e' = gf - g'.
    """
    if g.shape[0] == 0:
        return g, e
    gf = _per_tile(g.astype(jnp.float32) + e)
    scale = jnp.maximum(jnp.max(jnp.abs(gf), axis=1, keepdims=True),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127.0, 127.0)
    dq = q * scale
    return (dq.reshape(g.shape).astype(g.dtype),
            (gf - dq).reshape(g.shape))


def fused_topk_ef_ref(g: jax.Array, e: jax.Array, *,
                      fraction: float = 0.05, iters: int = 24
                      ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for ``fused_compress.fused_topk_ef``: per-tile magnitude
    top-k by the same count-curve bisection the kernel unrolls, so the
    kept set matches the kernel exactly (not merely approximately)."""
    if g.shape[0] == 0:
        return g, e
    gf = _per_tile(g.astype(jnp.float32) + e)
    mag = jnp.abs(gf)
    target = jnp.float32(fraction * mag.shape[1])
    lo = jnp.zeros((mag.shape[0], 1), jnp.float32)
    hi = jnp.max(mag, axis=1, keepdims=True) + jnp.float32(1e-12)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        keep = jnp.sum((mag >= mid).astype(jnp.float32), axis=1,
                       keepdims=True)
        take = keep >= target
        lo = jnp.where(take, mid, lo)
        hi = jnp.where(take, hi, mid)
    kept = jnp.where(mag >= lo, gf, 0.0)
    return (kept.reshape(g.shape).astype(g.dtype),
            (gf - kept).reshape(g.shape))
