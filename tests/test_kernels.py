"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels run in interpret mode on CPU (the TPU lowering is exercised
by the same pallas_call on real hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import interface, ops, ref, registry
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.fused_update import fused_update
from repro.kernels.interface import KernelType
from repro.kernels.rmsnorm import rmsnorm


# ----------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
@pytest.mark.parametrize("b,lq,lk,hq,hkv,d", [
    (1, 128, 128, 2, 2, 64),      # MHA square
    (2, 256, 256, 4, 2, 64),      # GQA
    (1, 128, 256, 4, 1, 128),     # MQA, lk > lq (suffix decode-ish)
])
def test_flash_attention_matches_ref(dtype, causal, window, b, lq, lk,
                                     hq, hkv, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, lq, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, lk, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, lk, hkv, d)).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_shape_invariance():
    """Output must not depend on the chosen BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    outs = [flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                                block_k=bk, interpret=True)
            for bq, bk in ((64, 64), (128, 64), (64, 128), (256, 256))]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5, rtol=1e-5)


def test_flash_attention_custom_vjp_grads():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 1, 64))
    v = jax.random.normal(ks[2], (1, 128, 1, 64))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v) ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("lq,lk", [(128, 128), (64, 256)])
@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 128)])
def test_flash_attention_bwd_matches_oracle_vjp(dtype, window, group, lq,
                                                lk, block_q, block_k):
    """The flash backward kernel (causal, optional window, GQA, queries
    end-aligned when lq < lk) against ``jax.vjp`` of the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, lq, 2 * group, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, lk, 2, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, lk, 2, 32)).astype(dtype)
    do = jax.random.normal(ks[3], q.shape).astype(dtype)
    o, vjp = jax.vjp(lambda *xs: ref.flash_attention_ref(
        *xs, causal=True, window=window), q, k, v)
    got = flash_attention_bwd(q, k, v, o, do, causal=True, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=True)
    _assert_close(got, vjp(do), dtype, bwd=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group", [1, 2])
def test_flash_attention_with_v_narrower_than_qk(dtype, group):
    """Latent attention's shapes (q/k 96 wide, v 64, scores over
    sqrt(96)): the forward, and the stats + backward kernels, against
    the oracle and its vjp.  Tolerances as the equal-width tests."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (1, 128, 2 * group, 96)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 96)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    do = jax.random.normal(ks[3], (1, 128, 2 * group, 64)).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=64,
                              block_k=64, interpret=True)
    o, vjp = jax.vjp(lambda *xs: ref.flash_attention_ref(*xs, causal=True),
                     q, k, v)
    assert out.shape == o.shape == (1, 128, 2 * group, 64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(o, np.float32), atol=tol, rtol=tol)
    got = flash_attention_bwd(q, k, v, o, do, causal=True, block_q=64,
                              block_k=64, interpret=True)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    _assert_close(got, vjp(do), dtype, bwd=True)


def test_registry_attention_grads_with_v_narrower_than_qk():
    """Through the registry's custom_vjp (the flash backward, as the
    counters show), gradients at v narrower than q/k match the oracle's."""
    from repro.perfcount import KERNELS
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 48))
    k = jax.random.normal(ks[1], (1, 128, 4, 48))
    v = jax.random.normal(ks[2], (1, 128, 4, 32))
    before = KERNELS.snapshot()
    g = jax.grad(lambda *xs: _sq(registry.attention(
        *xs, kernels="pallas")), argnums=(0, 1, 2))(q, k, v)
    assert KERNELS.delta(before)["attn_bwd_flash"] == 1
    gw = jax.grad(lambda *xs: _sq(ref.flash_attention_ref(*xs)),
                  argnums=(0, 1, 2))(q, k, v)
    _assert_close(g, gw, jnp.float32, bwd=True)


def test_attention_bwd_path_follows_the_shapes():
    """The backward takes the flash kernel where its blocks tile the
    sequence and the oracle's vjp where they do not (lq = 100), as the
    trace-time counters show; both match the oracle."""
    from repro.perfcount import KERNELS
    for l, path in ((128, "attn_bwd_flash"), (100, "attn_bwd_oracle")):
        ks = jax.random.split(jax.random.PRNGKey(l), 3)
        q = jax.random.normal(ks[0], (1, l, 4, 32))
        k = jax.random.normal(ks[1], (1, l, 2, 32))
        v = jax.random.normal(ks[2], (1, l, 2, 32))
        before = KERNELS.snapshot()
        g = jax.grad(lambda *xs: _sq(ops.flash_attention(*xs)),
                     argnums=(0, 1, 2))(q, k, v)
        assert KERNELS.delta(before) == {
            n: int(n == path) for n in before}, l
        gw = jax.grad(lambda *xs: _sq(ref.flash_attention_ref(*xs)),
                      argnums=(0, 1, 2))(q, k, v)
        _assert_close(g, gw, jnp.float32, bwd=True)


@given(
    lq_blocks=st.integers(1, 3),
    heads=st.sampled_from([(2, 2), (4, 2), (8, 1)]),
    d=st.sampled_from([64, 128]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(lq_blocks, heads, d, seed):
    hq, hkv = heads
    lq = 64 * lq_blocks
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, lq, hq, d))
    k = jax.random.normal(ks[1], (1, lq, hkv, d))
    v = jax.random.normal(ks[2], (1, lq, hkv, d))
    out = flash_attention_fwd(q, k, v, causal=True, block_q=64,
                              block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, expected, atol=3e-5, rtol=3e-5)


# ----------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 256), (2, 16, 512), (8, 3, 128)])
def test_rmsnorm_matches_ref(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:]).astype(dtype)
    out = rmsnorm(x, w, interpret=True)
    expected = ref.rmsnorm_ref(x, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)


@given(rows=st.integers(1, 17), d=st.sampled_from([128, 384, 768]),
       seed=st.integers(0, 2**16))
@settings(max_examples=15, deadline=None)
def test_rmsnorm_property(rows, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d))
    w = jnp.ones((d,))
    out = rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(out, ref.rmsnorm_ref(x, w),
                               atol=1e-5, rtol=1e-5)
    # invariant: rmsnorm output has unit RMS when weight == 1
    rms = np.sqrt(np.mean(np.square(np.asarray(out)), axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


# ----------------------------------------------------------- fused update
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4096,), (300,), (17, 129), (2, 3, 5)])
def test_fused_update_matches_ref(dtype, shape):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    m = jax.random.normal(ks[1], shape, jnp.float32)
    g = jax.random.normal(ks[2], shape).astype(dtype)
    po, mo = fused_update(p, m, g, lr=0.1, beta=0.9, scale=0.5,
                          interpret=True)
    pe, me = ref.fused_update_ref(p, m, g, lr=0.1, beta=0.9, scale=0.5)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pe, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(mo, me, atol=1e-5, rtol=1e-5)


def test_fused_update_traced_scalars_no_recompile():
    """lr/scale are data, not constants: one jit trace serves all values."""
    traces = 0

    @jax.jit
    def step(p, m, g, lr, scale):
        nonlocal traces
        traces += 1
        return fused_update(p, m, g, lr=lr, beta=0.9, scale=scale,
                            interpret=True)

    p = jnp.ones((1024,))
    m = jnp.zeros((1024,))
    g = jnp.ones((1024,))
    for lr, sc in ((0.1, 1.0), (0.2, 0.0), (0.01, 0.5)):
        po, mo = step(p, m, g, jnp.float32(lr), jnp.float32(sc))
        pe, me = ref.fused_update_ref(p, m, g, lr=lr, beta=0.9, scale=sc)
        np.testing.assert_allclose(po, pe, atol=1e-6)
    assert traces == 1


@given(n=st.integers(1, 5000), seed=st.integers(0, 2**16),
       beta=st.floats(0.0, 0.999))
@settings(max_examples=15, deadline=None)
def test_fused_update_property(n, seed, beta):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jax.random.normal(ks[0], (n,))
    m = jax.random.normal(ks[1], (n,))
    g = jax.random.normal(ks[2], (n,))
    po, mo = fused_update(p, m, g, lr=0.05, beta=beta, interpret=True)
    pe, me = ref.fused_update_ref(p, m, g, lr=0.05, beta=beta)
    np.testing.assert_allclose(po, pe, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(mo, me, atol=1e-5, rtol=1e-5)

# ================================================== registry parity grid
# Every registry op, every variant, fwd AND bwd (grads flow through the
# jax.custom_vjp pairing) vs. its kernels/ref.py oracle.  Pallas runs
# interpret=True here (CPU); the same dispatch compiles natively on TPU.

def _tols(dtype, bwd=False):
    if dtype == jnp.float32:
        return (1e-4, 1e-4) if bwd else (3e-5, 3e-5)
    # bf16 bwd: variants legitimately differ from the oracle by ~1 ulp
    # in the probs dtype for the PV matmul — allow a couple of ulps
    return (6e-2, 2e-2) if bwd else (2e-2, 2e-2)


def _assert_close(got, want, dtype, bwd=False):
    atol, rtol = _tols(dtype, bwd)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=atol, rtol=rtol)


def _sq(out):
    """Scalar loss over an array or tuple-of-arrays output (f32)."""
    return sum(jnp.sum(jnp.square(o.astype(jnp.float32)))
               for o in jax.tree_util.tree_leaves(out))


def _attention_inputs(dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    return q, k, v


def _norm_inputs(dtype):
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 256)).astype(dtype)
    r = jax.random.normal(jax.random.PRNGKey(9), (4, 256)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(10), (256,)).astype(dtype)
    return x, r, w


def _ssm_inputs(dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    b, l, di, ds = 2, 64, 4, 8
    u = jax.random.normal(ks[0], (b, l, di)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, l, di))) \
        .astype(dtype)
    a = -jax.nn.softplus(jax.random.normal(ks[2], (di, ds)))
    bmat = jax.random.normal(ks[3], (b, l, ds)).astype(dtype)
    cmat = jax.random.normal(ks[4], (b, l, ds)).astype(dtype)
    h0 = jnp.zeros((b, di, ds), jnp.float32)
    return u, delta, a, bmat, cmat, h0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["pallas", "xla"])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
def test_registry_attention_parity(dtype, variant, causal, window):
    q, k, v = _attention_inputs(dtype)
    spec = f"attention={variant}"
    out = registry.attention(q, k, v, causal=causal, window=window,
                             kernels=spec)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    _assert_close(out, want, dtype)
    g = jax.grad(lambda *xs: _sq(registry.attention(
        *xs, causal=causal, window=window, kernels=spec)),
        argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(lambda *xs: _sq(ref.flash_attention_ref(
        *xs, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    _assert_close(g, gw, dtype, bwd=True)


def test_registry_attention_pallas_block_fallback():
    """lq=100 divides no _BLOCKS entry: PALLAS must fall back to the XLA
    formulation, never raise, and still match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (1, 100, 2, 64))
    k = jax.random.normal(ks[1], (1, 100, 2, 64))
    v = jax.random.normal(ks[2], (1, 100, 2, 64))
    out = registry.attention(q, k, v, causal=True, kernels="pallas")
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["pallas", "xla"])
def test_registry_rmsnorm_parity(dtype, variant):
    x, _, w = _norm_inputs(dtype)
    spec = f"rmsnorm={variant}"
    out = registry.rmsnorm(x, w, kernels=spec)
    _assert_close(out, ref.rmsnorm_ref(x, w), dtype)
    g = jax.grad(lambda x_, w_: _sq(registry.rmsnorm(
        x_, w_, kernels=spec)), argnums=(0, 1))(x, w)
    gw = jax.grad(lambda x_, w_: _sq(ref.rmsnorm_ref(x_, w_)),
                  argnums=(0, 1))(x, w)
    _assert_close(g, gw, dtype, bwd=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["pallas", "xla"])
def test_registry_residual_rmsnorm_parity(dtype, variant):
    x, r, w = _norm_inputs(dtype)
    spec = f"residual_rmsnorm={variant}"
    out = registry.residual_rmsnorm(x, r, w, kernels=spec)
    want = ref.residual_rmsnorm_ref(x, r, w)
    assert len(out) == 2 and out[0].dtype == x.dtype
    _assert_close(out, want, dtype)
    g = jax.grad(lambda *xs: _sq(registry.residual_rmsnorm(
        *xs, kernels=spec)), argnums=(0, 1, 2))(x, r, w)
    gw = jax.grad(lambda *xs: _sq(ref.residual_rmsnorm_ref(*xs)),
                  argnums=(0, 1, 2))(x, r, w)
    _assert_close(g, gw, dtype, bwd=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["pallas", "xla", "xla_associative"])
@pytest.mark.parametrize("chunk", [16, 64, 7])   # 7: forced to l
def test_registry_ssm_scan_parity(dtype, variant, chunk):
    u, delta, a, bmat, cmat, h0 = _ssm_inputs(dtype)
    spec = f"ssm_scan={variant}"
    y, h = registry.ssm_scan(u, delta, a, bmat, cmat, h0, chunk=chunk,
                             kernels=spec)
    yw, hw = ref.ssm_scan_ref(u, delta, a, bmat, cmat, h0)
    assert y.dtype == u.dtype and h.dtype == jnp.float32
    _assert_close((y, h), (yw, hw), dtype)
    g = jax.grad(lambda *xs: _sq(registry.ssm_scan(
        *xs, h0, chunk=chunk, kernels=spec)),
        argnums=(0, 1, 2, 3, 4))(u, delta, a, bmat, cmat)
    gw = jax.grad(lambda *xs: _sq(ref.ssm_scan_ref(*xs, h0)),
                  argnums=(0, 1, 2, 3, 4))(u, delta, a, bmat, cmat)
    _assert_close(g, gw, dtype, bwd=True)


# ================================================== dispatch resolution
def test_dispatch_auto_tpu_picks_pallas_everywhere():
    for op in interface.OPS:
        assert interface.resolve("auto", op, tpu=True) is KernelType.PALLAS


def test_dispatch_auto_off_tpu_matches_historical_paths():
    assert interface.resolve("auto", "attention", tpu=False) \
        is KernelType.XLA
    assert interface.resolve("auto", "rmsnorm", tpu=False) is KernelType.XLA
    assert interface.resolve("auto", "residual_rmsnorm", tpu=False) \
        is KernelType.XLA
    assert interface.resolve("auto", "ssm_scan", tpu=False) \
        is KernelType.XLA_ASSOCIATIVE


def test_dispatch_bare_variant_applies_to_every_op():
    for op in interface.OPS:
        assert interface.resolve("pallas", op, tpu=False) \
            is KernelType.PALLAS
        assert interface.resolve("xla", op, tpu=True) is KernelType.XLA


def test_dispatch_per_op_override_composes_with_auto():
    spec = "ssm_scan=xla_associative,attention=pallas"
    assert interface.resolve(spec, "ssm_scan", tpu=True) \
        is KernelType.XLA_ASSOCIATIVE
    assert interface.resolve(spec, "attention", tpu=False) \
        is KernelType.PALLAS
    # untouched ops keep their auto resolution
    assert interface.resolve(spec, "rmsnorm", tpu=False) is KernelType.XLA
    assert interface.resolve(spec, "rmsnorm", tpu=True) \
        is KernelType.PALLAS


@pytest.mark.parametrize("bad", [
    "xla_associative",            # bare: attention has no such variant
    "attention=xla_associative",  # per-op: not implemented for this op
    "flash=pallas",               # unknown op
    "attention=cuda",             # unknown variant
    "attention",                  # missing '='
])
def test_dispatch_rejects_invalid_spec_listing_overrides(bad):
    with pytest.raises(ValueError) as e:
        interface.parse_kernels(bad)
    msg = str(e.value)
    assert interface.valid_overrides() in msg  # lists valid overrides


def test_registry_resolved_uses_live_backend():
    want_tpu = jax.default_backend() == "tpu"
    assert registry.resolved("attention", "auto") \
        is interface.resolve("auto", "attention", tpu=want_tpu)
    assert registry.resolved("ssm_scan", "pallas") is KernelType.PALLAS


def test_registry_pallas_runs_xla_inside_a_sharded_step():
    """GSPMD cannot partition a Mosaic kernel: under axis rules with a
    mesh every PALLAS pick resolves to the op's off-TPU formulation."""
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import rules_for_mesh, use_rules
    mesh = make_mesh((1, 1), ("data", "model"))
    with use_rules(rules_for_mesh(mesh)):
        for op in interface.OPS:
            assert registry.resolved(op, "pallas") \
                is interface.resolve("auto", op, tpu=False)
    assert registry.resolved("rmsnorm", "pallas") is KernelType.PALLAS
