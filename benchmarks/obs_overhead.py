"""Observability overhead: the price of the ``repro.obs`` recorder.

Three measurements, emitted as ``BENCH_obs.json``:

  1. **disabled_ns_per_call** — cost of one ``TRACE.instant`` call with
     the recorder disabled.  This is the number every instrumented hot
     path (push/pull/apply/frame codec) pays per event site when
     tracing is off; the contract is "a branch and a return".
  2. **events_per_sec_drained** — sustained record+drain throughput
     with the recorder enabled (ring capacity bounds memory, so this is
     the rate at which a traced run can emit before dropping).
  3. **hotpath off/on** — the same externally-driven pull+push loop
     against a one-shard server over the inproc endpoint, once with
     tracing off and once with it on; reports both ``perfcount``
     deltas.  The gate (``perf_gate.py --obs``) fails if the deltas
     differ — instrumentation must never add counted hot-path work —
     or if ``events_recorded_off`` is non-zero.

Run: ``PYTHONPATH=src python benchmarks/obs_overhead.py [--smoke]``.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.api import (ModelSpec, OptimizerSpec, RunSpec, ServerSpec,
                       SyncSpec, TransportSpec, build_session)
from repro.obs.trace import TRACE
from repro.perfcount import TRANSPORT, WIRE, snapshot_all
from repro.wireformat import WIRE_LANES

SCHEMA = "obs_overhead/v1"


def bench_disabled(n_calls: int) -> float:
    """ns per TRACE.instant call with the recorder disabled."""
    TRACE.disable()
    # Warm the attribute lookups once so the loop measures the call.
    TRACE.instant("push", worker=0)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        TRACE.instant("push", worker=0, clock=1)
    dt = time.perf_counter() - t0
    return dt / n_calls * 1e9


def bench_enabled_drain(n_events: int) -> float:
    """Events/sec through record+drain with the recorder enabled."""
    TRACE.enable(source="bench")
    t0 = time.perf_counter()
    for i in range(n_events):
        TRACE.instant("push", worker=0, clock=i)
        if (i + 1) % 4096 == 0:
            TRACE.drain()
    TRACE.drain()
    dt = time.perf_counter() - t0
    TRACE.disable()
    return n_events / dt


def _hotpath_once(n_rounds: int) -> dict:
    """Drive pull+push rounds against a one-shard server over the
    inproc endpoint; return the perfcount deltas for the loop."""
    params = {"w": np.arange(2048, dtype=np.float32),
              "b": np.ones(256, dtype=np.float32)}
    spec = RunSpec(
        model=ModelSpec(arch="custom"),
        optimizer=OptimizerSpec(lr=0.01),
        sync=SyncSpec(mode="asp"),
        ps=ServerSpec(kind="sharded", shards=1, workers=1),
        transport=TransportSpec(kind="inproc", endpoint=True))
    session = build_session(spec, params=params,
                            external_workers=True).start()
    try:
        client = session.transport.connect(0)
        rows = client.hello()
        grads = np.random.RandomState(0).randn(
            rows, WIRE_LANES).astype(np.float32)
        # Warm-up round: first apply compiles the fused kernel.
        client.pull_packed(copy=False)
        client.push_packed(grads)
        before = snapshot_all()
        for _ in range(n_rounds):
            client.pull_packed(copy=False)
            client.push_packed(grads)
        after = snapshot_all()
        client.bye()
        client.close()
    finally:
        session.close()
    return {group: {k: after[group][k] - before[group][k]
                    for k in after[group]}
            for group in after}


def bench_hotpath(n_rounds: int) -> dict:
    """The off/on comparison the perf gate checks."""
    WIRE.reset()
    TRANSPORT.reset()
    TRACE.disable()
    off = _hotpath_once(n_rounds)
    events_off = len(TRACE)

    TRACE.enable(source="bench")
    on = _hotpath_once(n_rounds)
    events_on = len(TRACE.drain())
    TRACE.disable()
    return {"off": off, "on": on, "identical": off == on,
            "events_off": events_off, "events_on": events_on}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing: fewer calls/rounds")
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args()

    n_calls = 50_000 if args.smoke else 200_000
    n_events = 50_000 if args.smoke else 200_000
    n_rounds = 16 if args.smoke else 64

    disabled_ns = bench_disabled(n_calls)
    drained_per_s = bench_enabled_drain(n_events)
    hotpath = bench_hotpath(n_rounds)

    report = {
        "schema": SCHEMA,
        "disabled_ns_per_call": disabled_ns,
        "events_per_sec_drained": drained_per_s,
        "events_recorded_off": hotpath.pop("events_off"),
        "events_recorded_on": hotpath.pop("events_on"),
        "hotpath": hotpath,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    print("name,value,unit")
    print(f"disabled_instant,{disabled_ns:.1f},ns/call")
    print(f"enabled_drain,{drained_per_s:.0f},events/s")
    print(f"hotpath_identical,{int(hotpath['identical'])},bool")
    print(f"events_recorded_off,{report['events_recorded_off']},events")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
