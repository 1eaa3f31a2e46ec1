"""Push/pull hot-path microbenchmark on the packed wire.

Measures the SERVER-side cost of one push / one pull through the
sharded parameter server:

  * ``packed``      push_packed       — the zero-repack path: the wire
                    buffer is sliced into per-shard views, no packing,
  * ``packed+int8`` the same with fused wire compression (ONE launch
                    per shard),
  * ``coalesced_W{N}``  N concurrent workers pushing into a coalescing
                    window of N: one ``fused_update_batched`` launch
                    per shard per ROUND instead of per push —
                    ``launches_per_round`` is the gated contract,
  * ``delta_W{N}``  N workers each advancing one shard, then one
                    version-delta pull: ``delta_bytes_per_pull`` vs
                    ``full_bytes_per_pull`` (bytes ∝ change).

Wall time on this container is interpret-mode dominated and mostly
meaningless; the *event counts* (``repro.perfcount``) are
backend-independent and are what the packed format keeps at zero:
``repack_events`` = packs + unpacks + per-leaf concats per push.

Emits machine-readable ``BENCH_push_pull.json`` plus the standard
``name,us_per_call,derived`` CSV on stdout.  ``--smoke`` runs a tiny
model + few pushes for the tier-1 CI workflow.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (ModelSpec, OptimizerSpec, RunSpec, ServerSpec,
                       SyncSpec, WireSpec, build_session)
from repro.perfcount import WIRE


def tail_heavy_tree(scale: int = 1) -> Dict[str, jax.Array]:
    """A few big matrices + a long tail of small leaves (biases, norms,
    per-layer scalars) — the shape profile where per-leaf dispatch
    overhead dominates the update phase."""
    rng = np.random.RandomState(0)
    tree: Dict[str, jax.Array] = {}
    for i in range(2 * scale):
        tree[f"w{i}"] = jnp.asarray(
            rng.randn(256 * scale, 128).astype(np.float32))
    for i in range(24 * scale):           # the tail
        tree[f"b{i}"] = jnp.asarray(rng.randn(64).astype(np.float32))
        tree[f"g{i}"] = jnp.asarray(rng.randn(8, 8).astype(np.float32))
        tree[f"s{i}"] = jnp.float32(rng.randn())
    return tree


def _grads_like(tree, seed: int):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32))
        if p.shape else jnp.float32(rng.randn()), tree)


def _session(params, n_shards: int, compression: str = "none",
             workers: int = 1, coalesce: int = 1,
             coalesce_wait_ms=None, delta_pull: bool = False):
    """One externally-driven session per measured path: the spec picks
    the compression and coalescing, the bench pushes payloads at the
    session's server directly."""
    spec = RunSpec(
        model=ModelSpec(arch="custom"),
        optimizer=OptimizerSpec(name="momentum", lr=0.01, momentum=0.9),
        sync=SyncSpec(mode="asp"),
        ps=ServerSpec(kind="sharded", shards=n_shards, workers=workers,
                      coalesce=coalesce,
                      coalesce_wait_ms=coalesce_wait_ms),
        wire=WireSpec(compression=compression, delta_pull=delta_pull))
    return build_session(spec, params=params,
                         external_workers=True).start()


def bench_path(params, grads_seq, n_shards: int, path: str,
               n_pushes: int) -> Dict[str, object]:
    session = _session(params, n_shards,
                       compression="int8" if path.endswith("+int8")
                       else "none")
    server = session.server
    payloads = [server.plan.pack(g) for g in grads_seq]
    push, pull = server.push_packed, server.pull_packed

    def block_server():
        # Drain device work without touching the counted wire APIs.
        for st in server.shards:
            jax.block_until_ready(st._packed_p)

    push(0, payloads[0])                      # warm up compile caches
    pull(0)
    block_server()

    WIRE.reset()
    t0 = time.monotonic()
    for i in range(n_pushes):
        push(0, payloads[(i + 1) % len(payloads)])
    block_server()
    push_wall = time.monotonic() - t0
    push_events = WIRE.snapshot()

    pull_wall = 0.0
    pull_events = {k: 0 for k in push_events}
    for i in range(n_pushes):
        push(0, payloads[i % len(payloads)])  # invalidate snapshot caches
        block_server()
        before = WIRE.snapshot()
        t0 = time.monotonic()
        jax.block_until_ready(pull(0))
        pull_wall += time.monotonic() - t0
        for k, v in WIRE.delta(before).items():
            pull_events[k] += v

    def per(ev):
        return {k: v / n_pushes for k, v in ev.items()}

    pe, le = per(push_events), per(pull_events)
    repack = pe["packs"] + pe["unpacks"] + pe["leaf_concats"]
    session.close()
    return {
        "path": path, "shards": n_shards, "n_pushes": n_pushes,
        "push_ms": 1e3 * push_wall / n_pushes,
        "pull_ms": 1e3 * pull_wall / n_pushes,
        "per_push": pe,
        "per_pull": le,
        "repack_events_per_push": repack,
        "pallas_calls_per_push": pe["pallas_calls"],
    }


def bench_coalesced(params, grads_seq, n_shards: int, workers: int,
                    n_rounds: int) -> Dict[str, object]:
    """W concurrent pushers into a coalescing window of W: the gated
    contract is ``launches_per_round == n_shards`` (one batched launch
    per shard per round, not per push)."""
    import threading

    # A generous linger makes the round deterministic on loaded CI
    # runners: the flusher waits for all W contributors (they are all
    # pushing concurrently) instead of racing the scheduler.
    session = _session(params, n_shards,
                       workers=workers, coalesce=workers,
                       coalesce_wait_ms=5000.0 if workers > 1 else 0.0)
    server = session.server
    wires = [server.plan.pack(g) for g in grads_seq]

    def round_once(measure_idx: int):
        threads = [threading.Thread(
            target=server.push_packed,
            args=(w, wires[(measure_idx + w) % len(wires)]))
            for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    round_once(0)                           # warm up compile caches
    for st in server.shards:
        jax.block_until_ready(st._packed_p)
    WIRE.reset()
    t0 = time.monotonic()
    for i in range(n_rounds):
        round_once(i + 1)
    for st in server.shards:
        jax.block_until_ready(st._packed_p)
    wall = time.monotonic() - t0
    ev = WIRE.snapshot()
    session.close()
    return {
        "path": f"coalesced_W{workers}", "shards": n_shards,
        "workers": workers, "n_rounds": n_rounds,
        "round_ms": 1e3 * wall / n_rounds,
        "launches_per_round": ev["pallas_calls"] / n_rounds,
        "launches_saved_per_round": ev["apply_launches_saved"] / n_rounds,
        "uncoalesced_launches_per_round": n_shards * workers,
    }


def bench_delta(params, grads_seq, n_shards: int, workers: int,
                n_pulls: int) -> Dict[str, object]:
    """W workers each advance one shard (w mod S), then one
    version-delta pull: bytes shipped vs the full snapshot."""
    session = _session(params, n_shards, workers=workers,
                       delta_pull=True)
    server = session.server
    layout = server.plan.wire_layout()
    itemsize = jnp.dtype(layout.dtype).itemsize
    full_bytes = layout.total_rows * 512 * itemsize
    shard_wires = [server.plan.shard_wires(server.plan.pack(g))
                   for g in grads_seq]
    touched = sorted({w % n_shards for w in range(workers)})

    d = server.pull_delta(0, None)          # bootstrap: full fallback
    versions = d.versions
    WIRE.reset()
    t0 = time.monotonic()
    for i in range(n_pulls):
        for w in range(workers):
            j = w % n_shards
            server.push_packed_shard(w, j,
                                     shard_wires[i % len(shard_wires)][j])
        d = server.pull_delta(0, versions)
        versions = d.versions
    wall = time.monotonic() - t0
    ev = WIRE.snapshot()
    session.close()
    delta_bytes = ev["delta_bytes_tx"] / n_pulls
    return {
        "path": f"delta_W{workers}", "shards": n_shards,
        "workers": workers, "n_pulls": n_pulls,
        "pull_ms": 1e3 * wall / n_pulls,
        "delta_bytes_per_pull": delta_bytes,
        "full_bytes_per_pull": full_bytes,
        "advanced_fraction": len(touched) / n_shards,
        "bytes_fraction": delta_bytes / full_bytes,
        "full_pull_bytes_avoided_per_pull":
            ev["full_pull_bytes_avoided"] / n_pulls,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tree + few pushes (CI tier-1)")
    ap.add_argument("--shards", type=int, nargs="*", default=None)
    ap.add_argument("--pushes", type=int, default=None)
    ap.add_argument("--workers", type=int, nargs="*", default=None,
                    help="worker counts for the coalesced/delta modes")
    ap.add_argument("--out", default="BENCH_push_pull.json")
    args = ap.parse_args()

    scale = 1 if args.smoke else 2
    shard_counts = args.shards or ([1, 4] if args.smoke else [1, 4, 16])
    worker_counts = args.workers or ([1, 4] if args.smoke else [1, 4, 8])
    n_pushes = args.pushes or (3 if args.smoke else 10)
    params = tail_heavy_tree(scale)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    grads_seq = [_grads_like(params, s) for s in range(2)]

    paths = ["packed", "packed+int8"]
    rows: List[Dict[str, object]] = []
    for s in shard_counts:
        for path in paths:
            rows.append(bench_path(params, grads_seq, s, path, n_pushes))

    # Coalesced-apply + version-delta modes: fixed shard count, swept
    # worker count (the axes the tentpole moves).
    cd_shards = min(4, max(shard_counts))
    for w in worker_counts:
        rows.append(bench_coalesced(params, grads_seq, cd_shards, w,
                                    n_pushes))
        rows.append(bench_delta(params, grads_seq, cd_shards, w,
                                n_pushes))

    # Derived acceptance metric: the packed path's repack events per
    # push at the largest shard count.
    s_max = max(shard_counts)
    by = {r["path"]: r for r in rows if r["shards"] == s_max}
    packed_ov = by["packed"]["repack_events_per_push"]
    co_rows = [r for r in rows if r["path"].startswith("coalesced")]
    de_rows = [r for r in rows if r["path"].startswith("delta")]
    coalesced_ok = all(r["launches_per_round"] <= r["shards"] + 1e-6
                       for r in co_rows)
    delta_ok = all(r["delta_bytes_per_pull"] < r["full_bytes_per_pull"]
                   for r in de_rows if r["advanced_fraction"] < 1.0)
    report = {
        "bench": "push_pull_latency",
        "smoke": args.smoke,
        "n_leaves": n_leaves,
        "total_params": int(sum(
            x.size for x in jax.tree_util.tree_leaves(params))),
        "shard_counts": shard_counts,
        "rows": rows,
        "derived": {
            "s_max": s_max,
            "repack_events_per_push_packed": packed_ov,
            # the zero-repack contract at the largest shard count
            "target_met": packed_ov == 0,
            # coalescing contract: batched-apply launches per round
            # scale with shards, not shards x workers
            "coalesced_target_met": coalesced_ok,
            # delta contract: pull bytes < full snapshot when < 100%
            # of shards advanced
            "delta_target_met": delta_ok,
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=float, allow_nan=False)

    print("name,us_per_call,derived")
    for r in rows:
        if r["path"].startswith("coalesced"):
            print(f"push_pull_{r['path']}_S{r['shards']},"
                  f"{1e3 * r['round_ms']:.0f},"
                  f"launches_per_round={r['launches_per_round']:.1f}"
                  f";uncoalesced={r['uncoalesced_launches_per_round']}")
        elif r["path"].startswith("delta"):
            print(f"push_pull_{r['path']}_S{r['shards']},"
                  f"{1e3 * r['pull_ms']:.0f},"
                  f"delta_bytes={r['delta_bytes_per_pull']:.0f}"
                  f";full_bytes={r['full_bytes_per_pull']}"
                  f";fraction={r['bytes_fraction']:.2f}")
        else:
            print(f"push_pull_{r['path']}_S{r['shards']},"
                  f"{1e3 * r['push_ms']:.0f},"
                  f"repack={r['repack_events_per_push']:.1f}"
                  f";launches={r['pallas_calls_per_push']:.1f}")
    print(f"# packed repack events/push at S={s_max}: {packed_ov:.1f} "
          f"(target 0: {report['derived']['target_met']})")
    print(f"# coalesced launches/round <= shards: {coalesced_ok}; "
          f"delta bytes < full on partial advance: {delta_ok}")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
