"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
blocks that break the (8, 128) tiling, unaligned dynamic slices, too
much VMEM.  Here each kernel is lowered and compiled by the TPU compiler
for a ``v5e:2x2`` topology that is described, not attached, at the
widths the chip runs: the ``h2o-danube-1.8b-chip`` worker step and its
packed server buffers, and ``ssm_scan`` at Jamba's d_inner / d_state.
Nothing executes; a test passes when the compiled program holds the
kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and the test
workers all import this file.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import fused_compress as fc
from repro.kernels import fused_update as fu
from repro.kernels import residual_rmsnorm as rrn
from repro.kernels import rmsnorm as rn
from repro.kernels import ssm_scan as scan
from repro.models import registry
from repro.wireformat import WIRE_LANES, WIRE_ROWS

CHIP = get_config("h2o-danube-1.8b-chip")
JAMBA = get_config("jamba-v0.1-52b")
MOON = get_config("moonlight-16b-a3b-chip")
SEQ = 2048
BATCH = 4
SHARDS = 2   # the larger shard region of a 2-4 shard server


def _shard_rows() -> int:
    """Rows of the larger packed shard region of the chip config."""
    per_shard = -(-registry.count_params(CHIP) // SHARDS)
    rows = -(-per_shard // WIRE_LANES)
    return -(-rows // WIRE_ROWS) * WIRE_ROWS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _attention_fwd(batch, seq):
    """The flash forward at chip widths and its argument shapes (q, k,
    v), with the blocks the registry picks for them."""
    hd = CHIP.resolved_head_dim
    q = ((batch, seq, CHIP.n_heads, hd), jnp.bfloat16)
    kv = ((batch, seq, CHIP.n_kv_heads, hd), jnp.bfloat16)
    bq, bk = fa.fwd_blocks(q[0], kv[0], jnp.bfloat16)
    return (functools.partial(fa.flash_attention_fwd, causal=True,
                              window=CHIP.sliding_window, block_q=bq,
                              block_k=bk), [q, kv, kv])


def test_flash_attention_compiles_at_chip_widths(one_chip):
    fn, shapes = _attention_fwd(BATCH, SEQ)
    assert (fn.keywords["block_q"], fn.keywords["block_k"]) == (1024, 1024)
    _compile(one_chip, fn, *shapes)


def _attention_bwd(batch, seq):
    """The flash backward at chip widths and its argument shapes
    (q, k, v, o, do), with the blocks it picks for them."""
    hd = CHIP.resolved_head_dim
    q = ((batch, seq, CHIP.n_heads, hd), jnp.bfloat16)
    kv = ((batch, seq, CHIP.n_kv_heads, hd), jnp.bfloat16)
    bq, bk = fa.bwd_blocks(q[0], kv[0], jnp.bfloat16)
    return (functools.partial(fa.flash_attention_bwd, causal=True,
                              window=CHIP.sliding_window, block_q=bq,
                              block_k=bk), [q, kv, kv, q, q])


def test_flash_attention_bwd_compiles_at_chip_widths(one_chip):
    fn, shapes = _attention_bwd(BATCH, SEQ)
    assert (fn.keywords["block_q"], fn.keywords["block_k"]) == (512, 512)
    _compile(one_chip, fn, *shapes)


def test_attention_grad_keeps_forward_reader_to_forward_calls(
        one_chip, monkeypatch):
    """``jax.grad`` of a remat'd attention call (the registry's custom_vjp
    with interpret off) at chip widths: the benchmark's forward reader
    matches exactly the two forward calls (primal and recompute) and
    none of the backward's, whose outputs are tuples."""
    import importlib.util
    bench = os.path.join(os.path.dirname(__file__), os.pardir,
                         "benchmarks")
    monkeypatch.syspath_prepend(bench)
    spec = importlib.util.spec_from_file_location(
        "attn_fwd_roofline",
        os.path.join(bench, "chip", "metrics", "attn_fwd_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with open(os.path.join(bench, "chip", "configs",
                           "danube-spmd.json")) as f:
        pattern = reader.pattern(json.load(f), SEQ)

    from repro.kernels.registry import _attention_pallas

    _, shapes = _attention_bwd(1, SEQ)
    blocks = fa.fwd_blocks(shapes[0][0], shapes[1][0], jnp.bfloat16)

    def loss(q, k, v):
        attn = jax.checkpoint(lambda *xs: _attention_pallas(
            *xs, True, CHIP.sliding_window, *blocks, False))
        return jnp.sum(jnp.square(attn(q, k, v).astype(jnp.float32)))

    text = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                    *shapes[:3])
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    matched = [ln for ln in calls if re.search(pattern, ln)]
    assert len(matched) == 2
    assert all("flash_attention_fwd" in ln for ln in matched)
    assert len(calls) == 4
    assert any("%flash_attention_bwd" in ln for ln in calls)
    assert any("%flash_attention_stats" in ln for ln in calls)


def _grids(fn, *shapes):
    """(grid, block shapes) of each pallas_call ``fn`` makes."""
    jaxpr = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(s, d)
                                 for s, d in shapes])
    return [(tuple(e.params["grid_mapping"].grid),
             [tuple(getattr(b, "block_size", None) for b in m.block_shape)
              for m in e.params["grid_mapping"].block_mappings])
            for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]


def test_flash_attention_grids_at_equal_widths_are_unchanged():
    """With v as wide as q/k (the danube cells), the kernels run the
    grids and blocks the registry picks at chip widths: the forward
    (b, hq, lq/1024, lk/1024) of 1024-row blocks (``fwd_blocks``); the
    stats pass (b, hq, 4, 4) and the backward (b, hkv, 4, group, 4) of
    512-row blocks, as before they took a narrower v."""
    hd = CHIP.resolved_head_dim
    fwd, bwd = _attention_bwd(BATCH, SEQ)
    blk, stat = (None, None, 512, hd), (None, None, 1, 512)
    fwd_fn, fwd_shapes = _attention_fwd(BATCH, SEQ)
    assert _grids(fwd_fn, *fwd_shapes) == [
        ((BATCH, 32, 2, 2), [(None, None, 1024, hd)] * 4)]
    assert _grids(fwd, *bwd) == [
        ((BATCH, 32, 4, 4), [blk] * 4 + [stat] * 2),
        ((BATCH, 8, 4, 4, 4), [blk] * 4 + [stat] * 2
         + [(None, 4, SEQ, hd), blk, blk])]


def test_flash_attention_compiles_at_latent_attention_widths(one_chip):
    """Moonlight's attention as the chip runs it: 16 heads, q/k 192 wide
    (128 + 64 rope), v 128, 2 x 8192 tokens, the forward and the
    backward (whose 512-row blocks fit the VMEM budget), each at the
    blocks the registry picks (the forward's 1024)."""
    dqk = MOON.qk_head_dim
    q = ((2, 8192, MOON.n_heads, dqk), jnp.bfloat16)
    v = ((2, 8192, MOON.n_heads, MOON.v_head_dim), jnp.bfloat16)
    bq, bk = fa.fwd_blocks(q[0], q[0], jnp.bfloat16, MOON.v_head_dim)
    assert (bq, bk) == (1024, 1024)
    _compile(one_chip, functools.partial(
        fa.flash_attention_fwd, causal=True, block_q=bq, block_k=bk),
        q, q, v)
    blocks = fa.bwd_blocks(q[0], q[0], jnp.bfloat16, MOON.v_head_dim)
    assert blocks == (512, 512)
    _compile(one_chip, functools.partial(
        fa.flash_attention_bwd, causal=True, block_q=512, block_k=512),
        q, q, v, v, v)


@pytest.mark.parametrize("rows", [BATCH * SEQ, SEQ - 1])  # 2047: % 8 != 0
def test_rmsnorm_compiles_for_any_row_count(one_chip, rows):
    _compile(one_chip, rn.rmsnorm, ((rows, CHIP.d_model), jnp.bfloat16),
             ((CHIP.d_model,), jnp.bfloat16))


@pytest.mark.parametrize("rows", [BATCH * SEQ, SEQ - 1])
def test_residual_rmsnorm_compiles_for_any_row_count(one_chip, rows):
    x = ((rows, CHIP.d_model), jnp.bfloat16)
    _compile(one_chip, rrn.residual_rmsnorm, x, x,
             ((CHIP.d_model,), jnp.bfloat16))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_update_compiles_at_shard_size(one_chip, dtype):
    buf = ((_shard_rows(), WIRE_LANES), dtype)
    _compile(one_chip, functools.partial(fu.fused_update, lr=0.01,
                                         beta=0.9), buf, buf, buf)


def test_fused_update_batched_compiles_at_shard_size(one_chip):
    rows = _shard_rows()
    buf = ((rows, WIRE_LANES), jnp.bfloat16)
    _compile(one_chip, functools.partial(
        fu.fused_update_batched, lr=0.01, beta=0.9,
        scales=(1.0, 0.5, 0.25, 0.125)),
        buf, buf, ((4, rows, WIRE_LANES), jnp.bfloat16))


def test_fused_int8_ef_compiles_at_shard_size(one_chip):
    rows = _shard_rows()
    _compile(one_chip, fc.fused_int8_ef,
             ((rows, WIRE_LANES), jnp.bfloat16),
             ((rows, WIRE_LANES), jnp.float32))


def test_ssm_scan_compiles_at_jamba_widths(one_chip):
    di, ds = JAMBA.expand * JAMBA.d_model, JAMBA.d_state
    seq = ((1, SEQ, di), jnp.bfloat16)
    bc = ((1, SEQ, ds), jnp.bfloat16)
    _compile(one_chip, scan.ssm_scan, seq, seq, ((di, ds), jnp.float32),
             bc, bc, ((1, di, ds), jnp.float32))


# ------------------------------------------------ kernel names on the chip
_NAMED = {
    "flash_attention_fwd": (functools.partial(
        fa.flash_attention_fwd, causal=True, window=CHIP.sliding_window),
        [((1, 256, CHIP.n_heads, CHIP.resolved_head_dim), jnp.bfloat16),
         ((1, 256, CHIP.n_kv_heads, CHIP.resolved_head_dim), jnp.bfloat16),
         ((1, 256, CHIP.n_kv_heads, CHIP.resolved_head_dim),
          jnp.bfloat16)]),
    "flash_attention_bwd": _attention_bwd(1, 256),
    "flash_attention_stats": _attention_bwd(1, 256),
    "rmsnorm": (rn.rmsnorm, [((64, CHIP.d_model), jnp.bfloat16),
                             ((CHIP.d_model,), jnp.bfloat16)]),
    "residual_rmsnorm": (rrn.residual_rmsnorm,
                         [((64, CHIP.d_model), jnp.bfloat16)] * 2
                         + [((CHIP.d_model,), jnp.bfloat16)]),
    "fused_momentum_apply_batched": (functools.partial(
        fu.fused_update_batched, lr=0.01, scales=(1.0, 0.5)),
        [((64, WIRE_LANES), jnp.bfloat16)] * 2
        + [((2, 64, WIRE_LANES), jnp.bfloat16)]),
    "fused_int8_ef": (fc.fused_int8_ef, [((64, WIRE_LANES), jnp.bfloat16),
                                         ((64, WIRE_LANES), jnp.float32)]),
    "fused_topk_ef": (fc.fused_topk_ef, [((64, WIRE_LANES), jnp.bfloat16),
                                         ((64, WIRE_LANES), jnp.float32)]),
    "ssm_scan": (scan.ssm_scan, [
        ((1, 256, 256), jnp.bfloat16), ((1, 256, 256), jnp.bfloat16),
        ((256, 16), jnp.float32), ((1, 256, 16), jnp.bfloat16),
        ((1, 256, 16), jnp.bfloat16), ((1, 256, 16), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(_NAMED))
def test_kernel_custom_call_is_named(one_chip, name):
    """A device trace names each kernel's custom call by its ``name=``,
    whatever program the kernel is compiled into."""
    fn, shapes = _NAMED[name]
    text = _compile(one_chip, fn, *shapes)
    assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name


def test_apply_kernel_named_by_its_program_on_the_recorded_trace(one_chip):
    """The apply kernel keeps the name of its program, ``_fused_update``
    (what the benchmark's apply reader matches), and its compiled
    instruction heads equal those of the ops a chip trace recorded, which
    is what the benchmark joins op paths on."""
    from repro.obs.device import module_op_paths, op_head
    buf = ((378880, WIRE_LANES), jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (buf, buf, buf)]
    compiled = fu._fused_update.lower(
        *args, 0.003, 1.0, beta=0.9, interpret=False).compile()
    heads = module_op_paths(compiled.as_text(), "jit__fused_update")
    excerpt = os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmarks", "chip", "testdata",
                           "trace_excerpt.json")
    with open(excerpt) as f:
        recorded = {e["name"] for e in json.load(f)["events"]
                    if e["name"].startswith(
                        ("%_fused_update.1 = (bf16[378880,",
                         "%maximum_bitcast_fusion = f32[1,2]"))}
    assert any(n.startswith("%_fused_update") for n in recorded)
    for name in recorded:
        assert heads[op_head(name)].startswith("jit(_fused_update)"), name
