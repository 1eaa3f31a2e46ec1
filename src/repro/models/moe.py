"""Mixture-of-Experts FFN (GShard-style capacity dispatch, expert-parallel).

Top-k routing with per-(row, chunk) capacity: the sequence is processed in
chunks via ``lax.scan`` so the dispatch/combine one-hot tensors stay small
(VMEM/HBM friendly), while expert weights are sharded over the 'model'
mesh axis (EP).  GSPMD inserts the token all-to-all at the
batch-sharded -> expert-sharded einsum boundary.

Supports DeepSeek-MoE style *shared experts* (always-on) next to the
routed ones, and emits the standard load-balancing auxiliary loss.

``held_moe_block`` is the other layer, for DeepSeek-V3's router
(``MoEConfig.router == "sigmoid_noaux"``): it routes over every expert
but holds only some of them, and computes without drops the part of the
output its held experts give, plus the shared experts.  Two dispatch
paths live side by side until the capacity path's users move over.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.models.sharding import shard
from repro.obs.device import phase

ROUTERS = ("softmax", "sigmoid_noaux")


def moe_defs(cfg: ModelConfig, n: int) -> Dict[str, ParamDef]:
    m = cfg.moe
    if m.router not in ROUTERS:
        raise ValueError(f"unknown router {m.router!r}; one of {ROUTERS}")
    if m.router == "softmax" and m.n_held != m.n_experts:
        raise ValueError("only the sigmoid_noaux layer holds a share of "
                         "the experts")
    d = cfg.d_model
    f = m.d_expert or cfg.d_ff
    e = m.n_held
    defs: Dict[str, ParamDef] = {
        "router": ParamDef((n, d, m.n_experts), (None, "fsdp", None),
                           fan_in_dims=(1,)),
        "w_gate": ParamDef((n, e, d, f), (None, "model", "fsdp", None),
                           fan_in_dims=(2,)),
        "w_up": ParamDef((n, e, d, f), (None, "model", "fsdp", None),
                         fan_in_dims=(2,)),
        "w_down": ParamDef((n, e, f, d), (None, "model", None, "fsdp"),
                           fan_in_dims=(2,)),
    }
    if m.n_shared:
        fs = f * m.n_shared
        defs["shared_gate"] = ParamDef((n, d, fs), (None, "fsdp", "model"),
                                       fan_in_dims=(1,))
        defs["shared_up"] = ParamDef((n, d, fs), (None, "fsdp", "model"),
                                     fan_in_dims=(1,))
        defs["shared_down"] = ParamDef((n, fs, d), (None, "model", "fsdp"),
                                       fan_in_dims=(1,))
    if m.router == "sigmoid_noaux":
        # DeepSeek-V3's e_score_correction_bias: it picks the experts and
        # weighs nothing; the gradient does not move it
        defs["router_bias"] = ParamDef((n, m.n_experts), (None, None),
                                       fan_in_dims=(1,))
    return defs


def _route(cfg: ModelConfig, x: jax.Array, router: jax.Array,
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (b, s, d) -> combine (b, s, e, c) f32, dispatch (same, model dtype),
    aux load-balance loss (scalar)."""
    m = cfg.moe
    e, k = m.n_experts, m.top_k
    s = x.shape[1]
    capacity = max(k, int(m.capacity_factor * s * k / e))

    logits = jnp.einsum("bsd,de->bse", x, router,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (b, s, e)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)             # (b, s, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)       # renormalize

    # Load-balance aux loss (Switch/GShard): e * Σ_e fraction_e · meanprob_e
    assign1 = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
    frac = jnp.mean(assign1, axis=(0, 1))
    meanp = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * meanp)

    # Position-in-expert per (row, chunk) group, k slots in priority order.
    combine = jnp.zeros((x.shape[0], s, e, capacity), jnp.float32)
    base = jnp.zeros((x.shape[0], 1, e), jnp.float32)           # used slots
    for j in range(k):
        onehot_e = jax.nn.one_hot(expert_idx[..., j], e,
                                  dtype=jnp.float32)            # (b, s, e)
        pos = jnp.cumsum(onehot_e, axis=1) - onehot_e + base    # (b, s, e)
        within = (pos < capacity) & (onehot_e > 0)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)              # (b,s,e,c)
        combine = combine + (gate_vals[..., j][..., None, None]
                             * within[..., None] * pos_oh * onehot_e[..., None])
        base = base + jnp.sum(onehot_e, axis=1, keepdims=True)
    dispatch = (combine > 0).astype(x.dtype)
    return combine, dispatch, aux


def _expert_ffn(cfg: ModelConfig, xe: jax.Array, w: Dict[str, Any]) -> jax.Array:
    """xe (e, b, c, d) expert-sharded -> (e, b, c, d)."""
    gate = jnp.einsum("ebcd,edf->ebcf", xe, w["w_gate"])
    up = jnp.einsum("ebcd,edf->ebcf", xe, w["w_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
    h = shard(h, "model", "batch", None, None)
    return jnp.einsum("ebcf,efd->ebcd", h, w["w_down"])


def _moe_chunk(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
               ) -> Tuple[jax.Array, jax.Array]:
    """Route+dispatch+compute+combine for one (b, chunk, d) slab."""
    combine, dispatch, aux = _route(cfg, x, w["router"])
    # batch-sharded -> expert-sharded (GSPMD all-to-all happens here)
    xe = jnp.einsum("bsd,bsec->ebcd", x, dispatch)
    xe = shard(xe, "model", "batch", None, None)
    ye = _expert_ffn(cfg, xe, w)
    y = jnp.einsum("ebcd,bsec->bsd", ye, combine.astype(x.dtype))
    return shard(y, "batch", None, None), aux


def moe_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
              ) -> Tuple[jax.Array, jax.Array]:
    """x (b, l, d) -> (y (b, l, d), aux scalar). Scans over seq chunks."""
    m = cfg.moe
    b, l, d = x.shape
    chunk = min(cfg.moe_chunk, l) if cfg.moe_chunk > 0 else l
    out_shared = _shared_experts(x, w) if m.n_shared else jnp.zeros_like(x)

    if chunk >= l or l % chunk != 0:   # decode / cost-mode: single dispatch
        y, aux = _moe_chunk(cfg, x, w)
        return y + out_shared, aux

    n_chunks = l // chunk
    xs = x.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)

    def step(_, xc):
        y, aux = _moe_chunk(cfg, xc, w)
        return (), (y, aux)

    _, (ys, auxs) = jax.lax.scan(step, (), xs)
    y = ys.transpose(1, 0, 2, 3).reshape(b, l, d)
    return y + out_shared, jnp.mean(auxs)


# =================================================== held-expert layer
def route_sigmoid(cfg: ModelConfig, x: jax.Array, router: jax.Array,
                  bias: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's router on tokens x (t, d): float32 sigmoid scores over
    every expert; the top-k of scores + ``bias`` are picked, and weighed
    by their unbiased scores, renormalised to sum 1 and times
    ``routed_scale``.  Returns (expert ids (t, k), weights (t, k) f32)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    pick = scores + jax.lax.stop_gradient(bias).astype(jnp.float32)
    _, idx = jax.lax.top_k(pick, m.top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx, gate * m.routed_scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, pos, k: int):
    """Rows of x (t, d) in the sorted order of the assignments: row r is
    token ``order[r] // k``.  Its transpose is a gather too: an
    assignment a takes row ``pos[a]`` (where ``pos[a] >= 0``), summed
    over a token's k assignments.  So it never reads the rows past the
    routed ones, which ``ragged_dot``'s gradient leaves unspecified on a
    TPU: ``jnp.take``'s own transpose, a scatter-add of every row, adds
    them onto the tokens."""
    return jnp.take(x, order // k, axis=0)


def _dispatch_fwd(x, order, pos, k):
    return _dispatch(x, order, pos, k), (pos, x.shape[0])


def _dispatch_bwd(k, res, dy):
    pos, t = res
    g = jnp.where((pos >= 0)[:, None],
                  jnp.take(dy, jnp.maximum(pos, 0), axis=0), 0)
    g = g.reshape(t, k, -1).sum(axis=1, dtype=jnp.float32)
    return g.astype(dy.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(rows, pos, order):
    """Per assignment a, row ``pos[a]`` of rows (r, d), zero where
    ``pos[a] < 0``; its transpose gathers the assignments back in row
    order (``order``), zero for rows ``order`` marks -1."""
    return jnp.where((pos >= 0)[:, None],
                     jnp.take(rows, jnp.maximum(pos, 0), axis=0), 0)


def _collect_fwd(rows, pos, order):
    return _collect(rows, pos, order), order


def _collect_bwd(order, dy):
    g = jnp.where((order >= 0)[:, None],
                  jnp.take(dy, jnp.maximum(order, 0), axis=0), 0)
    return g, None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _held_experts(x_rows: jax.Array, sizes: jax.Array, valid: jax.Array,
                  w: Dict[str, Any]) -> jax.Array:
    """SwiGLU of each held expert over its rows (grouped by expert, in
    expert order, ``sizes`` rows each) as grouped matmuls; rows past the
    groups (``valid`` False) come out zero."""
    dt = x_rows.dtype

    def grouped(a, b):
        return jax.lax.ragged_dot(a, b, sizes, preferred_element_type=dt)

    h = (jax.nn.silu(grouped(x_rows, w["w_gate"]).astype(jnp.float32))
         .astype(dt) * grouped(x_rows, w["w_up"]))
    h = jnp.where(valid[:, None], h, jnp.zeros((), dt))
    return jnp.where(valid[:, None], grouped(h, w["w_down"]),
                     jnp.zeros((), dt))


def held_moe_block(cfg: ModelConfig, x: jax.Array, w: Dict[str, Any],
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (b, l, d) -> (y (b, l, d), counts): the routed experts this chip
    holds (``MoEConfig.held`` from ``held_start``) and the shared experts.

    Every token is routed over all ``n_experts`` (``route_sigmoid``);
    its assignments to held experts are sorted by expert into a buffer
    of t * min(k, held) rows, which admits every assignment a token can
    make to them, so nothing is dropped whatever the routing.  The
    grouped matmuls (``jax.lax.ragged_dot``) run over that buffer, and
    each token sums its held experts' rows times their weights.  The
    output leaves out what the experts held elsewhere give: on one chip
    there is no exchange.  ``counts``: ``held_rows`` (assignments to
    held experts), ``tokens_routed`` and ``held_rows_max`` (the busiest
    held expert's rows), int32 scalars."""
    m = cfg.moe
    b, l, d = x.shape
    t, k, n_held = b * l, m.top_k, m.n_held
    xf = x.reshape(t, d)
    with phase("moe_route"):
        idx, gate = route_sigmoid(cfg, xf, w["router"], w["router_bias"])
        local = idx.reshape(-1) - m.held_start                   # (t k,)
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)[:t * min(k, n_held)]
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)
        n_rows = jnp.sum(sizes)
        rows = jnp.arange(order.shape[0])
        valid = rows < n_rows
        pos = jnp.full((t * k,), -1, jnp.int32).at[order].set(
            jnp.where(valid, rows, -1).astype(jnp.int32),
            unique_indices=True)
        x_rows = _dispatch(xf, order, pos, k)
    with phase("moe_experts"):
        out_rows = _held_experts(x_rows, sizes, valid, w)
    with phase("moe_route"):
        per = _collect(out_rows, pos, jnp.where(valid, order, -1))
        y = jnp.sum(per.reshape(t, k, d) * gate.astype(x.dtype)[..., None],
                    axis=1, dtype=jnp.float32)
    y = y.astype(x.dtype).reshape(b, l, d)
    if m.n_shared:
        y = y + _shared_experts(x, w)
    counts = {"held_rows": n_rows, "tokens_routed": jnp.int32(t),
              "held_rows_max": jnp.max(sizes)}
    return y, counts


def _shared_experts(x: jax.Array, w: Dict[str, Any]) -> jax.Array:
    gate = jnp.einsum("bld,df->blf", x, w["shared_gate"])
    up = jnp.einsum("bld,df->blf", x, w["shared_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = shard(h, "batch", None, "model")
    return jnp.einsum("blf,fd->bld", h, w["shared_down"])
