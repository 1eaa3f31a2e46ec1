"""Enum-dispatched kernel registry: ONE call site per worker-step hot op.

Each public function here is the single entry point the models call for
its op — ``attention``, ``rmsnorm``, ``residual_rmsnorm``, ``ssm_scan``
— dispatched over ``KernelType`` variants (``repro.kernels.interface``)
by the validated ``model.kernels`` spec string:

    variant           what runs
    ----------------  -------------------------------------------------
    PALLAS            the Pallas kernel (native on TPU, interpret=True
                      everywhere else), wrapped in ``jax.custom_vjp``;
                      attention's backward is the flash backward kernel
                      (``attention_bwd``), the others recompute through
                      the matching ``kernels/ref.py`` oracle
    XLA               the jnp reference formulation (native autodiff) —
                      bit-identical to the oracle by construction
    XLA_ASSOCIATIVE   ssm_scan only: the chunked associative-scan
                      formulation (parallel within chunks, lax.scan
                      carry across) that ``models/ssm.py`` historically
                      inlined

Dispatch is resolved at trace time (the spec string and backend are
static), so a jitted step compiles exactly one variant per op.  The
``kernels/ref.py`` oracles stay the correctness contract for every
variant: tests/test_kernels.py sweeps the full (op, variant, dtype)
grid fwd AND bwd against them.

Fallback behavior is part of the contract: the PALLAS attention variant
requires blocks from ``flash_attention.fwd_blocks`` to tile both
sequence lengths (the flash kernel's grid constraint) and otherwise
falls back to the XLA formulation — never an error, never a silent
wrong answer.  Inside a sharded step every PALLAS pick runs the XLA
formulation (``resolved``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import residual_rmsnorm as _rrn
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssm_scan as _scan
from repro.kernels.interface import AUTO, KernelType, resolve
from repro.obs.device import phase
from repro.perfcount import KERNELS


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolved(op: str, kernels: str = AUTO) -> KernelType:
    """The variant a spec string picks for ``op`` on the live backend
    (what a jitted step will actually compile).  Exposed for dispatch
    tests and the kernel benchmark.

    GSPMD cannot partition a Mosaic kernel, so inside a sharded step
    (axis rules with a mesh in scope) a PALLAS pick runs the op's
    off-TPU XLA formulation instead — as the sharded attention paths
    always have."""
    kt = resolve(kernels, op, tpu=on_tpu())
    if kt is KernelType.PALLAS and _partitioned():
        return resolve(AUTO, op, tpu=False)
    return kt


def _partitioned() -> bool:
    from repro.models.sharding import current_rules
    rules = current_rules()
    return rules is not None and rules.mesh is not None


# ================================================================ attention
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention_pallas(q, k, v, causal: bool, window: Optional[int],
                      block_q: int, block_k: int, interpret: bool):
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)


def _attention_pallas_fwd(q, k, v, causal, window, block_q, block_k,
                          interpret):
    o = _attention_pallas(q, k, v, causal, window, block_q, block_k,
                          interpret)
    return o, (q, k, v, o)


def _attention_pallas_bwd(causal, window, block_q, block_k, interpret,
                          res, dout):
    with phase("attn_bwd"):
        return attention_bwd(*res, dout, causal=causal, window=window,
                             interpret=interpret)


_attention_pallas.defvjp(_attention_pallas_fwd, _attention_pallas_bwd)


def attention_bwd(q, k, v, o, dout, *, causal: bool,
                  window: Optional[int], interpret: bool):
    """(dq, dk, dv) of attention output ``o`` under cotangent ``dout``:
    the flash backward kernel where ``flash_attention.bwd_blocks``
    covers the shapes, else ``jax.vjp`` of the ``ref.py`` oracle.  Each
    trace of either path bumps its ``perfcount.KERNELS`` counter."""
    blocks = _fa.bwd_blocks(q.shape, k.shape, q.dtype, v.shape[-1])
    if blocks is None:
        KERNELS.attn_bwd_oracle += 1
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _ref.flash_attention_ref(
                q_, k_, v_, causal=causal, window=window), q, k, v)
        return vjp(dout)
    KERNELS.attn_bwd_flash += 1
    return _fa.flash_attention_bwd(q, k, v, o, dout, causal=causal,
                                   window=window, block_q=blocks[0],
                                   block_k=blocks[1], interpret=interpret)


def _attention_xla(q, k, v, *, causal: bool, window: Optional[int]):
    """Quadratic masked attention, the formulation ``models/layers.py``
    always ran on the unsharded path (f32 scores/softmax, probs cast to
    v's dtype for the PV matmul) — kept bit-identical so ``auto`` off
    TPU preserves historical numerics."""
    b, lq, hq, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    qpos = jnp.arange(lq)[:, None] + (lk - lq)
    kpos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhlm,bmhd->blhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              kernels: str = AUTO) -> jax.Array:
    """q (b, lq, hq, d); k (b, lk, hkv, d); v (b, lk, hkv, dv) ->
    (b, lq, hq, dv); GQA broadcast; scores scaled by 1/sqrt(d);
    positions END-aligned (query i at absolute position lk - lq + i).

    PALLAS: the flash kernel (online softmax, (block_q, lk) working
    set) at the blocks ``flash_attention.fwd_blocks`` picks from the
    shapes; falls back to XLA where it finds none (lq or lk not a
    multiple of 8).  Each trace of either bumps its
    ``perfcount.KERNELS`` counter.  XLA: the quadratic masked
    formulation.  Its ops carry the ``attn_fwd`` device phase (its
    backward, ``attn_bwd``).
    """
    kt = resolved("attention", kernels)
    with phase("attn_fwd"):
        if kt is KernelType.PALLAS:
            blocks = _fa.fwd_blocks(q.shape, k.shape, q.dtype, v.shape[-1])
            if blocks is not None:
                KERNELS.attn_fwd_flash += 1
                return _attention_pallas(q, k, v, causal, window, *blocks,
                                         not on_tpu())
            KERNELS.attn_fwd_xla += 1
        return _attention_xla(q, k, v, causal=causal, window=window)


# ================================================================= rmsnorm
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_pallas(x, weight, eps: float):
    return _rn.rmsnorm(x, weight, eps=eps, interpret=not on_tpu())


def _rmsnorm_pallas_fwd(x, weight, eps):
    return _rmsnorm_pallas(x, weight, eps), (x, weight)


def _rmsnorm_pallas_bwd(eps, res, dout):
    x, weight = res
    _, vjp = jax.vjp(
        lambda x_, w_: _ref.rmsnorm_ref(x_, w_, eps), x, weight)
    return vjp(dout)


_rmsnorm_pallas.defvjp(_rmsnorm_pallas_fwd, _rmsnorm_pallas_bwd)


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            kernels: str = AUTO) -> jax.Array:
    """x (..., d), weight (d,) -> same shape/dtype as x; f32 reduction."""
    kt = resolved("rmsnorm", kernels)
    if kt is KernelType.PALLAS:
        return _rmsnorm_pallas(x, weight, eps)
    return _ref.rmsnorm_ref(x, weight, eps)


# ======================================================== residual+rmsnorm
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _residual_rmsnorm_pallas(x, res, weight, eps: float):
    return _rrn.residual_rmsnorm(x, res, weight, eps=eps,
                                 interpret=not on_tpu())


def _residual_rmsnorm_pallas_fwd(x, res, weight, eps):
    return _residual_rmsnorm_pallas(x, res, weight, eps), (x, res, weight)


def _residual_rmsnorm_pallas_bwd(eps, saved, dout):
    x, res, weight = saved
    _, vjp = jax.vjp(
        lambda x_, r_, w_: _ref.residual_rmsnorm_ref(x_, r_, w_, eps),
        x, res, weight)
    return vjp(dout)


_residual_rmsnorm_pallas.defvjp(_residual_rmsnorm_pallas_fwd,
                                _residual_rmsnorm_pallas_bwd)


def residual_rmsnorm(x: jax.Array, res: jax.Array, weight: jax.Array, *,
                     eps: float = 1e-6, kernels: str = AUTO
                     ) -> Tuple[jax.Array, jax.Array]:
    """Fused pre-norm block glue: ``s = x + res`` (f32) ->
    ``(s, rms_norm(s) * weight)``, both in x's dtype.  ``s`` is the
    residual stream the next sublayer adds onto; the normed output
    feeds the current one."""
    kt = resolved("residual_rmsnorm", kernels)
    if kt is KernelType.PALLAS:
        return _residual_rmsnorm_pallas(x, res, weight, eps)
    return _ref.residual_rmsnorm_ref(x, res, weight, eps)


# ================================================================ ssm scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssm_scan_pallas(u, delta, a, bmat, cmat, h0, chunk: int):
    return _scan.ssm_scan(u, delta, a, bmat, cmat, h0, chunk=chunk,
                          interpret=not on_tpu())


def _ssm_scan_pallas_fwd(u, delta, a, bmat, cmat, h0, chunk):
    return (_ssm_scan_pallas(u, delta, a, bmat, cmat, h0, chunk),
            (u, delta, a, bmat, cmat, h0))


def _ssm_scan_pallas_bwd(chunk, saved, dout):
    u, delta, a, bmat, cmat, h0 = saved
    _, vjp = jax.vjp(_ref.ssm_scan_ref, u, delta, a, bmat, cmat, h0)
    return vjp(dout)


_ssm_scan_pallas.defvjp(_ssm_scan_pallas_fwd, _ssm_scan_pallas_bwd)


def _ssm_scan_associative(u, delta, a, bmat, cmat, h0, chunk: int):
    """Chunked associative scan (the formulation ``models/ssm.py``
    historically inlined as ``_ssm_chunked``): within a chunk the
    recurrence composes via ``jax.lax.associative_scan`` on
    (A-product, B-accumulate) pairs; a ``lax.scan`` carries the state
    across chunk boundaries, bounding the materialized state to
    (chunk, di, ds) instead of (l, di, ds).  All math in f32."""
    b, l, di = u.shape
    ds = a.shape[-1]
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    af = a.astype(jnp.float32)
    da = df[..., None] * af[None, None]                        # (b,l,di,ds)
    abar = jnp.exp(da)
    bbar = df[..., None] * bmat.astype(jnp.float32)[:, :, None, :] \
        * uf[..., None]

    nc = max(1, l // chunk)
    abar = abar.reshape(b, nc, chunk, di, ds)
    bbar = bbar.reshape(b, nc, chunk, di, ds)
    cseq = cmat.astype(jnp.float32).reshape(b, nc, chunk, ds)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def chunk_step(h, xs):
        ac, bc, cc = xs              # (b, chunk, di, ds) x2, (b, chunk, ds)
        acc_a, acc_b = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        hs = acc_a * h[:, None] + acc_b
        y = jnp.einsum("bcds,bcs->bcd", hs, cc)
        return hs[:, -1], y

    h_last, ys = jax.lax.scan(
        chunk_step, h0.astype(jnp.float32),
        (abar.transpose(1, 0, 2, 3, 4), bbar.transpose(1, 0, 2, 3, 4),
         cseq.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3).reshape(b, l, di)
    return y.astype(u.dtype), h_last


def ssm_scan(u: jax.Array, delta: jax.Array, a: jax.Array,
             bmat: jax.Array, cmat: jax.Array, h0: jax.Array, *,
             chunk: int = 128, kernels: str = AUTO
             ) -> Tuple[jax.Array, jax.Array]:
    """Selective scan (Mamba S6): ``h_t = exp(delta_t A) h_{t-1} +
    delta_t B_t u_t; y_t = C_t . h_t``.

    u/delta (b, l, di); a (di, ds); bmat/cmat (b, l, ds); h0 (b, di, ds)
    -> (y (b, l, di) in u's dtype, h_last (b, di, ds) f32).  ``chunk``
    is clamped to l and forced to l when it does not divide.
    """
    l = u.shape[1]
    chunk = min(chunk, l) if chunk > 0 else l
    if l % chunk:
        chunk = l
    kt = resolved("ssm_scan", kernels)
    if kt is KernelType.PALLAS:
        return _ssm_scan_pallas(u, delta, a, bmat, cmat, h0, chunk)
    if kt is KernelType.XLA_ASSOCIATIVE:
        return _ssm_scan_associative(u, delta, a, bmat, cmat, h0, chunk)
    return _ref.ssm_scan_ref(u, delta, a, bmat, cmat, h0)
