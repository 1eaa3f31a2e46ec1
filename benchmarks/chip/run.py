#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json`` on the chips it asks for.

    python3 benchmarks/chip/run.py --workload danube-ps.solo \\
        --seed 7 --seconds 30 --trace 0

One process loads the cell's configuration and traffic files (found by
name under ``configs/`` and ``traffic/``) and the model family the
configuration names (``families/``), builds the program's session,
warms it up and checks its first steps against the float32 reference's
inputs, then measures for ``--seconds``.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces a window with the
profiler and reports the per-layer metrics, each read by its own file
under ``metrics/``.  After the window the program is freed and the
reference replays the first steps; the comparison decides ``correct``.
The numbers compared are printed beside their limits as the last lines
of standard error and under ``checks`` in the result.

The last line of standard output is one JSON object.  A run that finds
no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE.parent)]

#: Longest window a traced run records: the trace is read back in the
#: same process, and its size grows with the window.
TRACE_SECONDS = 6.0


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:8.2f}] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def by_name(entries, name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: Path, name: str):
    """The benchmark, and the workload ``name`` with its configuration,
    traffic and limits, each found by name in a file of its own."""
    bench = load_json(root / "BENCHMARK.json")
    work = by_name(bench["workloads"], name, "workload")
    conf = by_name(bench["configs"], work["config"], "config")
    here = root / bench["paths"][0]
    return (bench, work, load_json(root / conf["file"]),
            load_json(here / "traffic" / f"{work['traffic']}.json"),
            load_json(here / "limits" / f"{work['name']}.json"))


def load_reader(name: str, here: Path = HERE):
    """A per-layer metric's reader: ``metrics/<name>.py``, ``read(ctx)``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"this cell needs {n} TPU chip(s); JAX found "
                         f"{len(devs)} x {devs[0].platform} "
                         "(there is no CPU fallback)")
    return devs


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def measure(cell, seconds: float, compiles):
    """The window: the program's own loop for ``seconds`` (to the next
    step's end); nothing may compile inside it."""
    compiles.active = True
    a = cell.counters()
    if hasattr(cell, "drive"):
        cell.drive(a["t"] + seconds)
    else:
        time.sleep(max(0.0, a["t"] + seconds - time.monotonic()))
    b = cell.counters()
    compiles.active = False
    return dict(cell.window_numbers(a, b), t0=a["t"], t1=b["t"])


def trajectory(cell, start: float, end: float, points: int = 64):
    """The window's step losses as [seconds into the window, loss], at
    most ``points`` of them, evenly picked."""
    got = [(t - start, x) for t, x in cell.trajectory if start <= t <= end]
    step = max(1, -(-len(got) // points))
    return [[round(t, 3), x] for t, x in got[::step]]


def traced(cell, seconds: float, n_chips: int, compiles):
    """A window under the profiler, with the program's ``repro.obs``
    spans on; returns (window numbers, trace context)."""
    import jax
    from repro.obs.trace import TRACE

    from chip import tracefold
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        TRACE.enable(source="bench")
        jax.profiler.start_trace(tdir)
        pc0 = TRACE.now()
        with jax.profiler.TraceAnnotation(tracefold.ANCHOR):
            pass
        TRACE.span("metrics_snapshot", pc0, args={"bench_anchor": True})
        pc_a = time.perf_counter()
        nums = measure(cell, seconds, compiles)
        pc_b = time.perf_counter()
        jax.profiler.stop_trace()
        spans = TRACE.drain()
        TRACE.disable()
        (path,) = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        events = tracefold.events_from_xspace(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    at = tracefold.anchor_ns(events)
    (anchor,) = [s for s in spans if s["name"] == "metrics_snapshot"
                 and s.get("args", {}).get("bench_anchor")]
    window = (at + (pc_a - pc0) * 1e9, at + (pc_b - pc0) * 1e9)
    ops = tracefold.device_ops(events, window)
    host = tracefold.spans_on_profile_clock(
        [s for s in spans if s is not anchor], pc0, anchor["ts"], at)
    return nums, {
        "ops": ops, "window": window, "window_s": (pc_b - pc_a),
        "busy_s": tracefold.busy_seconds(ops, n_chips),
        "breakdown": tracefold.breakdown(ops, window, host),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, work, cfg, traffic, limits = load_cell(ROOT, args.workload)
    devs = require_chips(int(work["chips"]))
    log(f"compile cache {use_compile_cache()}")
    result = run_cell(bench, work, cfg, traffic, limits, args, devs)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, work, cfg, traffic, limits, args, devs,
             require_tpu: bool = True, here: Path = HERE):
    """Set-up, window, reference; returns the result object.  The
    family and the readers are found under ``here``."""
    from chip import cells, peaks
    n_chips = int(work["chips"])
    kind = devs[0].device_kind
    pk = peaks.peak(kind) if require_tpu else None
    family = cells.family_of(cfg, here)
    cells.check_program_config(cfg, family)
    engine = cells.ENGINES[cfg["program"]["engine"]]
    cell = engine(cfg, traffic, args.seed, family)
    compiles = cells.CompileCounter()
    log(f"{work['name']}: {engine.engine} seed {args.seed} "
        f"sub-seeds {cell.seeds}")
    cell.setup()
    setup_s = time.monotonic() - T_START
    log(f"set-up {setup_s:.2f} s")

    ctx = None
    if args.trace:
        nums, ctx = traced(cell, min(args.seconds, TRACE_SECONDS), n_chips,
                           compiles)
    else:
        nums = measure(cell, args.seconds, compiles)
    stats = devs[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    log(f"window {nums} compiles {compiles.count} "
        f"memory peak {peak_bytes}")

    checks = dict(cell.finish())
    import jax
    live = jax.live_arrays()
    log(f"freed the program: {len(live)} arrays, "
        f"{sum(x.nbytes for x in live)} bytes still live")
    del live
    compared = cell.reference()
    for name, value in compared.items():
        checks[name] = (value, limits[name])
    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in
              checks.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    if args.trace:
        ctx.update(model=cell.m, family=family, traffic=traffic,
                   config=cfg, engine=engine.engine, peak=pk,
                   n_chips=n_chips, counters=nums,
                   memory_peak_bytes=peak_bytes)
        metrics = {}
        for m in bench["per_layer"]:
            if not applies(m, work["name"]):
                continue
            got = load_reader(m["name"], here)(ctx)
            if got is None:
                continue
            entry = got if isinstance(got, dict) else {"value": got}
            metrics[m["name"]] = dict(entry, unit=m["unit"])
    else:
        values = {"tokens_per_s": nums["tokens"] / nums["seconds"],
                  "setup_s": setup_s,
                  "staleness_mean": nums.get("staleness_mean")}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if applies(m, work["name"])}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": n_chips, "memory_peak_bytes": peak_bytes}
    out = {"correct": correct,
           "attempted": int(nums.get("pushes", nums["tokens"]
                                     // cell.tokens_per_step)),
           "failed": int(checks.get("lost_pushes", {}).get("value", 0)),
           "metrics": metrics, "device": device}
    if args.trace:
        device.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        out["breakdown"] = ctx["breakdown"]
    out["compiles_in_window"] = compiles.count
    out["loss_trajectory"] = trajectory(cell, nums["t0"], nums["t1"])
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
