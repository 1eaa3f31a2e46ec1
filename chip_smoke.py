#!/usr/bin/env python3
"""Chip smoke run: the DSSP parameter-server trainer on one TPU at
h2o-danube-1.8b's published widths, through the entry points a user calls.

    python chip_smoke.py               # one chip: phases 1-4 below
    python chip_smoke.py --four-chips  # four chips: phase 5 only

1. ps-threads — ``build_session`` on ``h2o-danube-1.8b-chip`` (8 of 24
   layers, every width published): a sharded server (4 shards) with the
   fused Pallas apply, 2 workers on the packed wire, DSSP gating, seq
   2048, for 8 pushes.  Losses are finite, every shard applied every
   push, and the store and each worker's output are on the TPU.
2. kernels — the compiled worker step and server apply hold Pallas
   kernels (``tpu_custom_call``): nothing fell back to XLA.
3. parity — one worker step at ``kernels='auto'`` (Pallas) against
   ``kernels='xla'`` on the same parameters and batch, and one server
   apply against the ``kernels/ref.py`` oracle.
4. spmd — ``train.py``'s default engine (DSSP delayed-gradient
   pipeline, AdamW) for a few steps, with a 2-slot ring (s_upper=1).
5. four chips — the sharded train step on a (2, 2) ('data', 'model')
   mesh against the same step on one chip, with the parameter bytes
   each device holds.

Any failure exits non-zero.  The last line of stdout is one JSON object
naming the device as JAX reports it.  Everything runs in this one
process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b-chip"
SEQ = 2048
SHARDS, WORKERS, PUSHES = 4, 2, 8
PS_BATCH = 2          # rows per worker step (memory: see the config file)
SPMD_BATCH, SPMD_STEPS, SPMD_S_UPPER = 1, 4, 1
FOUR_CHIP_BATCH = 4
# bf16 keeps 8 significant bits (a relative step of 2^-8 = 0.4%).  The
# Pallas and XLA paths round at different points of the same math, so
# the scalar loss may move by a few such steps; a broken kernel moves
# the gradient by O(1).
LOSS_RTOL = 1e-2
GRAD_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu() -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{backend!r} (there is no CPU fallback)")


def _platforms(tree) -> set:
    return {d.platform for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


def _config(arch: str, smoke: bool):
    from repro.configs import get_config, get_smoke_config
    return get_smoke_config(arch) if smoke else get_config(arch)


@jax.jit
def _rel_l2(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(a - b))
                    / jnp.maximum(jnp.sum(jnp.square(b)), 1e-30))


def _ulp_error(got, want):
    """(elements not bitwise equal, max |got - want| in units of the
    dtype's epsilon at the buffer's largest magnitude).  The kernel and
    the oracle do the same f32 math and round once to the storage dtype;
    where the two compilers contract a multiply-add differently, a result
    that cancels can differ by more than one ulp of itself but never by
    more than one ulp of the largest operand."""
    eps = float(jnp.finfo(got.dtype).eps)
    a, b = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)
    return int(jnp.sum(a != b)), float(err) / eps


def _kernels_in(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _memory(label: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[{label}] device memory: peak "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB, in use "
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


# ------------------------------------------------------------- phase 1
def ps_phase(*, platform: str, arch: str = ARCH, smoke: bool = False,
             seq: int = SEQ, batch: int = PS_BATCH, shards: int = SHARDS,
             workers: int = WORKERS, pushes: int = PUSHES):
    """Train through the threaded parameter server; returns the open
    session (the caller closes it)."""
    from repro import api
    spec = api.RunSpec(
        model=api.ModelSpec(arch=arch, smoke=smoke),
        data=api.DataSpec(seq_len=seq, global_batch=batch),
        optimizer=api.OptimizerSpec(momentum=0.9),
        sync=api.SyncSpec(mode="dssp"),
        ps=api.ServerSpec(kind="sharded", shards=shards, workers=workers,
                          apply="fused"),
        wire=api.WireSpec(format="packed"))
    cfg = _config(arch, smoke)
    from repro.models import registry
    log(f"[ps] {spec.engine}: {cfg.name} {cfg.n_layers} layers d_model "
        f"{cfg.d_model} heads {cfg.n_heads}/{cfg.n_kv_heads} d_ff "
        f"{cfg.d_ff} vocab {cfg.vocab_size} window {cfg.sliding_window}; "
        f"{registry.count_params(cfg):,} params ({cfg.dtype}); shards "
        f"{shards} workers {workers} seq {seq} batch {batch}/step, "
        f"wire {spec.wire.format}, apply {spec.ps.apply}, "
        f"sync {spec.sync.mode} [{spec.sync.s_lower}, "
        f"{spec.sync.s_upper}]")
    session = api.build_session(spec).start()
    try:
        m = session.run(pushes)
        server = session.server
        first, last = m["first_loss"], m["final_loss"]
        log(f"[ps] pushes {m['pushes']} shard versions "
            f"{server.shard_versions()} (server version {server.version})"
            f" max staleness {m['max_staleness']} loss first {first!r} "
            f"last {last!r}")
        check(m["pushes"] == pushes, f"{m['pushes']} pushes != {pushes}")
        check(server.shard_versions() == [pushes] * shards,
              f"shard versions {server.shard_versions()} != {pushes} "
              "applied pushes each")
        check(first is not None and math.isfinite(first)
              and last is not None and math.isfinite(last),
              f"non-finite loss: first {first} last {last}")
        store = _platforms(server.pull_packed())
        log(f"[ps] server store on {sorted(store)}; worker outputs on "
            f"{list(session.worker_platforms)}")
        check(store == {platform}, f"server store on {store}")
        check(list(session.worker_platforms) == [platform] * workers,
              f"worker outputs on {session.worker_platforms}")
    except BaseException:
        session.close()
        raise
    return session


# ----------------------------------------------------------- phases 2-3
def parity_phase(session, *, compiled_kernels: bool) -> None:
    """Kernel presence (on a TPU) and the two comparisons, on the live
    server's parameters."""
    from repro.data.synthetic import DataConfig, batches
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.models import registry
    spec, server = session.spec, session.server
    plan = server.plan
    cfg = _config(spec.model.arch, spec.model.smoke)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size,
                          seq_len=spec.data.seq_len,
                          global_batch=spec.data.global_batch,
                          seed=spec.data.seed + 99)
    batch = {k: jnp.asarray(v)
             for k, v in next(batches(cfg, data_cfg)).items()}
    wire_p = server.pull_packed()
    step = session.worker_step
    if compiled_kernels:
        n = _kernels_in(step.lower(wire_p, jnp.zeros_like(wire_p),
                                   batch).compile())
        log(f"[kernels] worker step: {n} tpu_custom_call")
        check(n > 0, "the compiled worker step holds no Pallas kernel")
    g_auto, aux = step(wire_p, jnp.zeros_like(wire_p), batch)
    loss_auto = float(aux["loss"])

    loss_xla_fn = registry.loss_fn(dataclasses.replace(cfg, kernels="xla"))

    @jax.jit
    def xla_step(wp, b):
        (loss, _), grads = jax.value_and_grad(
            loss_xla_fn, has_aux=True)(plan.unpack(wp), b)
        return plan.pack(grads), loss

    g_xla, loss_xla = xla_step(wire_p, batch)
    loss_xla = float(loss_xla)
    grad_err = float(_rel_l2(g_auto, g_xla))
    log(f"[parity] worker step loss auto {loss_auto!r} xla {loss_xla!r} "
        f"(rel {abs(loss_auto - loss_xla) / max(1.0, abs(loss_xla)):.3e},"
        f" bound {LOSS_RTOL}); gradient rel-L2 {grad_err:.3e} "
        f"(bound {GRAD_RTOL})")
    check(math.isfinite(loss_auto) and math.isfinite(loss_xla),
          "non-finite parity loss")
    check(abs(loss_auto - loss_xla) <= LOSS_RTOL * max(1.0, abs(loss_xla)),
          f"loss auto {loss_auto} vs xla {loss_xla}")
    check(grad_err <= GRAD_RTOL, f"gradient rel-L2 {grad_err}")

    # one server apply (shard 0's region) against the oracle
    p0, m0, g0 = (plan.shard_wire(x, 0) for x in (wire_p, g_xla, g_auto))
    opt = spec.optimizer
    apply = functools.partial(kops.fused_update, lr=opt.lr,
                              beta=opt.momentum, scale=0.5)
    if compiled_kernels:
        n = _kernels_in(jax.jit(apply).lower(p0, m0, g0).compile())
        log(f"[kernels] server apply: {n} tpu_custom_call")
        check(n > 0, "the compiled server apply holds no Pallas kernel")
    got = apply(p0, m0, g0)
    want = ref.fused_update_ref(p0, m0, g0, lr=opt.lr, beta=opt.momentum,
                                scale=0.5)
    (dp, ep), (dm, em) = (_ulp_error(a, b) for a, b in zip(got, want))
    log(f"[parity] server apply on {p0.shape} {p0.dtype} vs the oracle: "
        f"p {dp} elements differ, max {ep:.3f} ulp of max|p|; m {dm} "
        f"differ, max {em:.3f} ulp of max|m|")
    check(ep <= 1.0 and em <= 1.0, f"server apply vs oracle: p {ep} ulp, "
          f"m {em} ulp")


# ------------------------------------------------------------- phase 4
def spmd_phase(*, arch: str = ARCH, smoke: bool = False, seq: int = SEQ,
               batch: int = SPMD_BATCH, steps: int = SPMD_STEPS,
               s_upper: int = SPMD_S_UPPER) -> None:
    from repro import api
    spec = api.RunSpec(
        model=api.ModelSpec(arch=arch, smoke=smoke),
        data=api.DataSpec(seq_len=seq, global_batch=batch),
        sync=api.SyncSpec(mode="dssp", s_lower=0, s_upper=s_upper))
    cfg = _config(arch, smoke)
    log(f"[spmd] {spec.engine}: {cfg.name} optimizer {cfg.optimizer}, "
        f"sync {spec.sync.mode} s_upper {s_upper} (ring of {s_upper + 1} "
        f"gradient copies), seq {seq} batch {batch}, {steps} steps")
    with api.build_session(spec) as session:
        m = session.run(steps)
        where = _platforms(session.trainer.params)
    losses = session.trainer.log.losses
    log(f"[spmd] losses {losses} mean delay {m['mean_delay']} params on "
        f"{sorted(where)}")
    check(m["steps"] == steps, f"{m['steps']} spmd steps != {steps}")
    check(all(math.isfinite(x) for x in losses), f"spmd losses {losses}")


# ------------------------------------------------------------- phase 5
def four_chip_mesh(devices):
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 2), ("data", "model"), devices=devices)


def four_chip_phase(devices, *, arch: str = ARCH, smoke: bool = False,
                    seq: int = SEQ, batch: int = FOUR_CHIP_BATCH) -> None:
    """The sharded train step on a (2, 2) mesh against one chip."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.models import registry
    from repro.models.params import spec_tree
    from repro.models.sharding import rules_for_mesh, use_rules
    cfg = _config(arch, smoke)
    one = devices[0]
    params = jax.device_put(
        registry.init_params(cfg, jax.random.PRNGKey(0)), one)
    toks = jax.device_put(jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size), one)
    b = {"tokens": toks, "labels": toks}
    lfn = registry.loss_fn(cfg)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, bt: lfn(p, bt)[0]))(params, b)
    ref_loss = float(ref_loss)

    mesh = four_chip_mesh(devices)
    rules = rules_for_mesh(mesh)
    specs = spec_tree(registry.param_defs(cfg), rules)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    params_sh = jax.device_put(params, shardings)
    b_sh = jax.device_put(b, NamedSharding(mesh, PartitionSpec("data")))

    def loss_fn(p, bt):
        with use_rules(rules):
            return lfn(p, bt)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params_sh, b_sh)
    loss = float(loss)
    held = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(params_sh):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat_sh = [jax.device_put(g, one)
               for g in jax.tree_util.tree_leaves(grads)]
    grad_err = max(float(_rel_l2(a, r))
                   for a, r in zip(flat_sh, flat_ref))
    log(f"[four-chips] mesh {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}; parameter bytes per device "
        f"{held} of {total} in all")
    log(f"[four-chips] loss sharded {loss!r} one chip {ref_loss!r}; "
        f"worst per-leaf gradient rel-L2 {grad_err:.3e} "
        f"(bound {GRAD_RTOL})")
    check(all(0 < n < total for n in held.values()),
          f"parameters do not span the mesh: {held}")
    check(abs(loss - ref_loss) <= LOSS_RTOL * max(1.0, abs(ref_loss)),
          f"sharded loss {loss} vs one chip {ref_loss}")
    check(grad_err <= GRAD_RTOL, f"gradient rel-L2 {grad_err}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded step on a (2, 2) mesh of "
                         "four chips against one chip")
    args = ap.parse_args(argv)
    require_tpu()
    from repro.launch.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.four_chips:
        if len(devices) < 4:
            raise SystemExit(f"--four-chips needs 4 devices, have "
                             f"{len(devices)}")
        four_chip_phase(devices[:4])
    else:
        session = ps_phase(platform="tpu")
        try:
            parity_phase(session, compiled_kernels=True)
            _memory("ps-threads")
        finally:
            session.close()
        del session
        gc.collect()
        spmd_phase()
        _memory("whole run")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
