#!/usr/bin/env python3
"""Record a traced window of one cell and fold its device time by phase.

    python3 benchmarks/chip/phase_record.py --workload danube-ps.solo \\
        --seed 7 --seconds 3 --out phase_records

Sets the cell up as ``run.py`` does, traces ``--seconds`` of its window
and folds the device time by phase the way the benchmark's readers do
(``phasefold``: the program's compiled HLO joined on the op names).
Writes ``<workload>.json`` under ``--out``: the fold, the ops of each
phase that took most time with their op paths, the hot-path counters
over the traced interval, the idle gaps, and an excerpt: the longest op
events of each phase with their paths (how the tests'
``testdata/trace_excerpt_phases.json`` was cut).  No reference replay:
this run decides nothing about ``correct``.
"""

import argparse
import collections
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]

from chip import cells, phasefold, run, tracefold  # noqa: E402


def top_ops(ops, path_of, phases, programs, n=8):
    """Per phase, the op labels that took most time, with a path."""
    acc = collections.defaultdict(lambda: collections.defaultdict(float))
    where = {}
    for plane in ops.values():
        for name, s, t in plane:
            if tracefold.CONTAINER.match(name):
                continue
            path = path_of(name)
            ph = (phasefold.phase_of(path, phases, programs) if path
                  else "unscoped")
            lab = tracefold.label(name)
            acc[ph][lab] += (t - s) / 1e9
            where[lab] = path
    return {ph: [[lab, secs, where[lab]] for lab, secs in
                 sorted(d.items(), key=lambda kv: -kv[1])[:n]]
            for ph, d in acc.items()}


def excerpt(events, window, path_of, phase, per_phase):
    """The ``per_phase`` longest device op events of each phase inside
    the window, in time order, each with its op path."""
    by_phase = collections.defaultdict(list)
    for e in events:
        if (tracefold.DEVICE_PLANE.match(e["plane"])
                and e["line"] == tracefold.DEVICE_OPS_LINE
                and window[0] <= e["ts"] < window[1]
                and not tracefold.CONTAINER.match(e["name"])):
            by_phase[phase(e["name"])].append(e)
    out = [dict(e, path=path_of(e["name"]))
           for evs in by_phase.values()
           for e in sorted(evs, key=lambda e: -e["dur"])[:per_phase]]
    return sorted(out, key=lambda e: e["ts"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--excerpt", type=int, default=20,
                    help="longest op events kept per phase")
    ap.add_argument("--out", default="phase_records")
    args = ap.parse_args(argv)

    bench, work, cfg, traffic, limits = run.load_cell(run.ROOT,
                                                      args.workload)
    run.require_chips(int(work["chips"]))
    run.use_compile_cache()
    out = record(cfg, traffic, args, int(work["chips"]))
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    with open(dest / f"{args.workload}.json", "w") as f:
        json.dump(out, f)
    print(json.dumps({k: out[k] for k in ("workload", "window_s", "busy_s",
                                          "phases", "window_counters")}))
    return 0


def record(cfg, traffic, args, n_chips: int):
    """Set the cell up, trace its window, fold it; the output object."""
    import jax

    from repro.obs import device
    from repro.obs.trace import FOLD_PHASES, PHASE_PROGRAMS, PHASES, TRACE

    engine = cells.ENGINES[cfg["program"]["engine"]]
    cell = engine(cfg, traffic, args.seed)
    compiles = cells.CompileCounter()
    cell.setup()
    run.log(f"set-up done, {args.workload}")

    tdir = tempfile.mkdtemp(prefix="phase-record-")
    try:
        TRACE.enable(source="bench")
        jax.profiler.start_trace(tdir)
        pc0 = TRACE.now()
        with jax.profiler.TraceAnnotation(tracefold.ANCHOR):
            pass
        TRACE.span("metrics_snapshot", pc0, args={"bench_anchor": True})
        pc_a = time.perf_counter()
        nums = run.measure(cell, args.seconds, compiles)
        pc_b = time.perf_counter()
        jax.profiler.stop_trace()
        spans = TRACE.drain()
        TRACE.disable()
        (path,) = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        events = tracefold.events_from_xspace(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    cell.finish()
    at = tracefold.anchor_ns(events)
    window = (at + (pc_a - pc0) * 1e9, at + (pc_b - pc0) * 1e9)
    ops = tracefold.device_ops(events, window)
    busy = tracefold.busy_seconds(ops, n_chips)
    paths = device.op_paths()

    def joined(name):
        return paths.get(device.op_head(name))

    def phase(name):
        path = joined(name)
        return (phasefold.phase_of(path, PHASES, PHASE_PROGRAMS) if path
                else "unscoped")

    fold = phasefold.fold(ops, joined, PHASES, PHASE_PROGRAMS, FOLD_PHASES)
    (anchor,) = [s for s in spans if s["name"] == "metrics_snapshot"]
    host = tracefold.spans_on_profile_clock(
        [s for s in spans if s is not anchor], pc0, anchor["ts"], at)
    out = {
        "workload": args.workload, "seed": args.seed,
        "window_s": pc_b - pc_a, "busy_s": busy, "counters": nums,
        "phases": fold,
        "top": top_ops(ops, joined, PHASES, PHASE_PROGRAMS),
        "window_counters": TRACE.window_counters(),
        "idle_gaps": tracefold.breakdown(ops, window, host)["idle_gaps"],
        "excerpt": excerpt(events, window, joined, phase, args.excerpt),
        "compiles_in_window": compiles.count,
    }
    return out


if __name__ == "__main__":
    sys.exit(main())
