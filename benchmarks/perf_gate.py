"""Perf-trajectory gate over the push/pull wire-format benchmark.

CI calls this with the fresh ``BENCH_push_pull.json`` and (when the
download step found one) the previous run's artifact.  Wall time on
shared runners is noise, so the gate is on the *event counts* — the
backend-independent per-push repack/launch numbers the packed format
exists to eliminate:

  1. zero-repack contract (absolute, always checked): the ``packed``
     path performs 0 host-side repack events per push at every shard
     count, and the derived ``target_met`` flag is true;
  2. coalescing contract (absolute): every ``coalesced_W*`` row does
     at most ``shards`` batched-apply launches per round — launch
     count scales with shards, never shards x workers;
  3. delta contract (absolute): every ``delta_W*`` row that advanced
     < 100% of shards ships fewer delta bytes than a full snapshot;
  4. trajectory (only with ``--previous``): for every (path, shards)
     row present in both reports, no gated metric —
     ``repack_events_per_push``, ``pallas_calls_per_push``,
     ``launches_per_round``, ``delta_bytes_per_pull`` — may increase;
     a PR may make the hot path cheaper, never quietly more chatty;
  5. observability (only with ``--obs``, see ``check_obs``): tracing
     off records 0 events and leaves perfcount hot-path deltas
     bitwise-identical to tracing on; the disabled-call cost may not
     regress versus ``--obs-previous``;
  6. serving (only with ``--serving``, see ``check_serving``): zero
     staleness-bound violations and a request stream that was actually
     served from advancing versions; latency/throughput may not blow
     up versus ``--serving-previous``;
  7. kernels (only with ``--kernels``, see ``check_kernels``): every
     registry (op, variant) pair is present, matches its ``ref.py``
     oracle absolutely, and its achieved step time may not blow up
     versus ``--kernels-previous``.

Exit code 1 on any violation (the CI job fails), 0 otherwise.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Tuple

#: Counting events, so exact equality is meaningful; the epsilon only
#: forgives float formatting, not a real extra event.
EPS = 1e-6

#: Rows carry the metrics that apply to their mode; absent ones skip.
GATED_METRICS = ("repack_events_per_push", "pallas_calls_per_push",
                 "launches_per_round", "delta_bytes_per_pull")


def _rows_by_key(report: dict) -> Dict[Tuple[str, int], dict]:
    return {(r["path"], int(r["shards"])): r for r in report["rows"]}


def check(current: dict, previous: dict | None) -> list:
    failures = []
    rows = _rows_by_key(current)
    for (path, shards), row in sorted(rows.items()):
        if path.startswith("packed") and \
                row["repack_events_per_push"] > EPS:
            failures.append(
                f"zero-repack contract broken: {path} at S={shards} does "
                f"{row['repack_events_per_push']:.2f} repack events/push "
                f"(expected 0)")
        if path.startswith("coalesced") and \
                row["launches_per_round"] > shards + EPS:
            failures.append(
                f"coalescing contract broken: {path} at S={shards} does "
                f"{row['launches_per_round']:.2f} apply launches/round "
                f"(expected <= {shards} — one batched launch per shard)")
        if path.startswith("delta") and \
                row.get("advanced_fraction", 1.0) < 1.0 - EPS and \
                row["delta_bytes_per_pull"] >= row["full_bytes_per_pull"]:
            failures.append(
                f"delta contract broken: {path} at S={shards} ships "
                f"{row['delta_bytes_per_pull']:.0f} bytes/pull with only "
                f"{row['advanced_fraction']:.0%} of shards advanced "
                f"(full snapshot is {row['full_bytes_per_pull']:.0f})")
    if not current.get("derived", {}).get("target_met", False):
        failures.append("derived.target_met is false "
                        "(the packed path repacked at the largest shard "
                        "count)")
    if previous is not None:
        prev_rows = _rows_by_key(previous)
        for key in sorted(set(rows) & set(prev_rows)):
            for metric in GATED_METRICS:
                now = rows[key].get(metric)
                before = prev_rows[key].get(metric)
                if now is None or before is None:
                    continue    # metric does not apply to this mode
                if now > before + EPS:
                    failures.append(
                        f"{key[0]} at S={key[1]}: {metric} regressed "
                        f"{before:.2f} -> {now:.2f}")
    return failures


def check_obs(current: dict, previous: dict | None) -> list:
    """Gate over ``BENCH_obs.json`` (``benchmarks/obs_overhead.py``).

    Absolute: tracing off must record 0 events and leave the hot-path
    perfcount deltas bitwise-identical to the traced run (the recorder
    never adds counted work).  Trajectory: the disabled-call cost may
    not blow up versus the previous artifact (generous bound — shared
    runners are noisy, but a 5x/+200ns jump means someone put real work
    ahead of the early-return).
    """
    failures = []
    if current.get("events_recorded_off", 0) != 0:
        failures.append(
            f"obs contract broken: {current['events_recorded_off']} "
            "events recorded with tracing disabled (expected 0)")
    hot = current.get("hotpath", {})
    if not hot.get("identical", False):
        failures.append(
            "obs contract broken: perfcount hot-path deltas differ "
            "between tracing-off and tracing-on runs "
            f"(off={hot.get('off')} on={hot.get('on')})")
    if previous is not None:
        now = current.get("disabled_ns_per_call")
        before = previous.get("disabled_ns_per_call")
        if now is not None and before is not None \
                and now > max(before * 5.0, before + 200.0):
            failures.append(
                f"disabled TRACE call cost regressed "
                f"{before:.0f}ns -> {now:.0f}ns per call")
        for group in ("wire", "transport"):
            cur_off = hot.get("off", {}).get(group, {})
            prev_off = (previous.get("hotpath", {})
                        .get("off", {}).get(group, {}))
            for k in sorted(set(cur_off) & set(prev_off)):
                if cur_off[k] > prev_off[k] + EPS:
                    failures.append(
                        f"tracing-off hot path got chattier: "
                        f"{group}.{k} {prev_off[k]} -> {cur_off[k]}")
    return failures


def check_recovery(current: dict, previous: dict | None) -> list:
    """Gate over ``BENCH_recovery.json`` (``benchmarks/recovery.py``).

    Absolute: the per-shard snapshot pause is bounded (no global
    stop-the-world hides in the capture path), the on-disk restore
    round-trips (``resume.ok``), and every client reconnected in one
    re-HELLO against an idle rebound listener.  When the report carries
    a ``reshard`` section (``recovery.py --reshard``): ZERO pushes lost
    or double-applied across the live migration, and the per-shard
    copy-out pause stays under the 0.5s acceptance bound.  Trajectory:
    the pause, restore time and migration time may not blow up versus
    the previous artifact (generous bounds — shared runners are noisy,
    but a 5x jump means the capture started holding locks across real
    work).
    """
    failures = []
    snap = current.get("snapshot", {})
    pause = snap.get("pause_per_shard_us_max")
    if pause is None:
        failures.append("recovery report carries no "
                        "snapshot.pause_per_shard_us_max")
    elif pause > 50_000.0:
        failures.append(
            f"snapshot pause contract broken: a shard's lock was held "
            f"{pause:.0f}us for capture (bound 50ms — the per-shard "
            "pause must stay bounded; is capture doing work under the "
            "lock?)")
    if not current.get("resume", {}).get("ok", False):
        failures.append("resume contract broken: restore_latest did not "
                        "round-trip the snapshotted server version")
    mean_rc = current.get("reconnect", {}).get("mean_reconnects")
    if mean_rc is not None and mean_rc > 1.0 + EPS:
        failures.append(
            f"reconnect contract broken: {mean_rc:.2f} reconnects/client "
            "against an idle rebound listener (expected exactly 1)")
    reshard = current.get("reshard")
    if reshard is not None:
        lost = reshard.get("lost")
        if lost is None:
            failures.append("reshard report carries no loss ledger")
        elif lost != 0:
            failures.append(
                f"reshard zero-loss contract broken: ledger reads "
                f"{lost} (parked={reshard.get('parked')} "
                f"replayed={reshard.get('replayed')} "
                f"sent={reshard.get('pushes_sent')} "
                f"applied={reshard.get('pushes_applied')}) — a push "
                "racing the migration was lost or double-applied")
        pause = reshard.get("pause_per_shard_us_max", 0.0)
        if pause > 500_000.0:
            failures.append(
                f"reshard pause contract broken: a shard's lock was "
                f"held {pause:.0f}us for copy-out (bound 0.5s — the "
                "migration must not stop the world)")
    if previous is not None:
        for path_, label in ((("snapshot", "pause_per_shard_us_max"),
                              "per-shard snapshot pause (us)"),
                             (("resume", "restore_ms"),
                              "restore wall time (ms)"),
                             (("reshard", "migration_ms"),
                              "live-reshard migration time (ms)")):
            sec, key = path_
            now = current.get(sec, {}).get(key)
            before = previous.get(sec, {}).get(key)
            if now is not None and before is not None \
                    and now > max(before * 5.0, before + 1000.0):
                failures.append(
                    f"{label} regressed {before:.1f} -> {now:.1f}")
    return failures


def check_serving(current: dict, previous: dict | None) -> list:
    """Gate over ``BENCH_serving.json`` (``benchmarks/serving.py``).

    Absolute: the freshness contract held — ZERO admissions above
    ``serve.staleness_bound`` (a single violation means the gate served
    stale weights), every closed-loop request was served, and the
    replicas decoded against a LIVE store (served versions advanced
    while the workers trained).  Trajectory: decode latency and
    throughput may not blow up versus the previous artifact (generous
    bounds — shared runners are noisy, but a 5x p99 jump means real
    work landed on the admission/decode path).
    """
    failures = []
    serve = current.get("serve", {})
    violations = serve.get("violations")
    if violations is None:
        failures.append("serving report carries no serve.violations")
    elif violations > 0:
        failures.append(
            f"freshness contract broken: {violations} admissions above "
            f"staleness_bound={current.get('staleness_bound')} — the "
            "admission gate served stale weights")
    if serve.get("requests", 0) <= 0:
        failures.append("serving contract broken: no requests were "
                        "served (replicas never came up?)")
    if serve.get("version_max", -1) <= 0:
        failures.append(
            "serving contract broken: served versions never advanced — "
            "replicas decoded a dead store while training ran")
    if serve.get("p99_ms") is None:
        failures.append("serving report carries no serve.p99_ms")
    if previous is not None:
        now_p99 = serve.get("p99_ms")
        before_p99 = previous.get("serve", {}).get("p99_ms")
        if now_p99 is not None and before_p99 is not None \
                and now_p99 > max(before_p99 * 5.0, before_p99 + 1000.0):
            failures.append(
                f"decode p99 latency regressed "
                f"{before_p99:.1f}ms -> {now_p99:.1f}ms")
        now_rps = serve.get("requests_per_s")
        before_rps = previous.get("serve", {}).get("requests_per_s")
        if now_rps is not None and before_rps is not None \
                and before_rps > 0 and now_rps < before_rps / 5.0:
            failures.append(
                f"serving throughput regressed "
                f"{before_rps:.1f} -> {now_rps:.1f} requests/s")
    return failures


#: every (op, variant) pair BENCH_kernels.json must cover — the full
#: registry surface minus ssm_scan's extra associative variant (which is
#: gated too when present, just not required).
REQUIRED_KERNEL_ROWS = tuple(
    (op, variant)
    for op in ("attention", "rmsnorm", "residual_rmsnorm", "ssm_scan")
    for variant in ("pallas", "xla"))

#: oracle parity bound for the f32 benchmark shapes (absolute max |err|).
KERNEL_PARITY_TOL = 5e-3


def check_kernels(current: dict, previous: dict | None) -> list:
    """Gate over ``BENCH_kernels.json`` (``roofline_table.py --kernels``).

    Absolute: every required (op, variant) row is present and its output
    matches the ``kernels/ref.py`` oracle to ``KERNEL_PARITY_TOL`` — a
    registry variant that drifts from the oracle is a wrong answer, not
    a perf problem.  Trajectory: a row's achieved step time may not blow
    up versus the previous artifact (generous bound — CPU interpret-mode
    timings on shared runners are noisy, but a 5x/+1s jump means real
    work landed on the dispatch path); rows or metrics missing from
    either side are skipped, never failed.
    """
    failures = []
    rows = {(r["op"], r["variant"]): r for r in current.get("rows", [])}
    for op, variant in REQUIRED_KERNEL_ROWS:
        if (op, variant) not in rows:
            failures.append(
                f"kernel coverage broken: no ({op}, {variant}) row in "
                "the benchmark report — the registry grid shrank")
    for (op, variant), row in sorted(rows.items()):
        err = row.get("parity_max_err")
        if err is None:
            failures.append(f"kernel row ({op}, {variant}) carries no "
                            "parity_max_err")
        elif err > KERNEL_PARITY_TOL:
            failures.append(
                f"kernel parity broken: {op}={variant} differs from its "
                f"ref.py oracle by {err:.2e} (tol {KERNEL_PARITY_TOL})")
        if row.get("achieved_ms") is None \
                or row.get("predicted_ms") is None:
            failures.append(
                f"kernel row ({op}, {variant}) misses achieved_ms/"
                "predicted_ms (achieved-vs-predicted contract)")
    if not current.get("derived", {}).get("parity_ok", False):
        failures.append("derived.parity_ok is false")
    if previous is not None:
        prev_rows = {(r["op"], r["variant"]): r
                     for r in previous.get("rows", [])}
        for key in sorted(set(rows) & set(prev_rows)):
            now = rows[key].get("achieved_ms")
            before = prev_rows[key].get("achieved_ms")
            if now is not None and before is not None \
                    and now > max(before * 5.0, before + 1000.0):
                failures.append(
                    f"{key[0]}={key[1]}: achieved step time regressed "
                    f"{before:.3f}ms -> {now:.3f}ms")
    return failures


def _load(path: str | None, label: str) -> dict | None:
    if not path:
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf-gate: no usable {label} artifact ({e}); "
              "checking absolute contract only")
        return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("current", nargs="?", default=None,
                    help="fresh BENCH_push_pull.json (optional when only "
                         "--recovery is gated, as in the chaos CI job)")
    ap.add_argument("--previous", default=None,
                    help="prior run's artifact (omit on first run)")
    ap.add_argument("--obs", default=None,
                    help="fresh BENCH_obs.json (adds the observability "
                         "overhead gate)")
    ap.add_argument("--obs-previous", default=None,
                    help="prior run's BENCH_obs.json artifact")
    ap.add_argument("--recovery", default=None,
                    help="fresh BENCH_recovery.json (adds the fault-"
                         "tolerance recovery gate)")
    ap.add_argument("--recovery-previous", default=None,
                    help="prior run's BENCH_recovery.json artifact")
    ap.add_argument("--serving", default=None,
                    help="fresh BENCH_serving.json (adds the online-"
                         "serving freshness gate)")
    ap.add_argument("--serving-previous", default=None,
                    help="prior run's BENCH_serving.json artifact")
    ap.add_argument("--kernels", default=None,
                    help="fresh BENCH_kernels.json (adds the kernel-"
                         "registry parity + step-time gate)")
    ap.add_argument("--kernels-previous", default=None,
                    help="prior run's BENCH_kernels.json artifact")
    args = ap.parse_args()
    if args.current is None and args.recovery is None \
            and args.serving is None and args.kernels is None:
        ap.error("nothing to gate: pass BENCH_push_pull.json and/or "
                 "--recovery and/or --serving and/or --kernels")

    failures = []
    previous = None
    if args.current is not None:
        with open(args.current) as f:
            current = json.load(f)
        previous = _load(args.previous, "previous")

        rows = _rows_by_key(current)
        prev_rows = _rows_by_key(previous) if previous else {}
        print(f"{'path':>18} {'S':>3}  gated metrics")
        for (path, shards), row in sorted(rows.items()):
            marks = []
            for metric in GATED_METRICS:
                now = row.get(metric)
                if now is None:
                    continue
                before = prev_rows.get((path, shards), {}).get(metric)
                marks.append(f"{metric}={now:.2f}"
                             + (f" (was {before:.2f})"
                                if before is not None else ""))
            print(f"{path:>18} {shards:>3}  {' '.join(marks)}")
        failures += check(current, previous)

    recovery = _load(args.recovery, "recovery")
    if recovery is not None:
        recovery_prev = _load(args.recovery_previous, "recovery-previous")
        snap = recovery.get("snapshot", {})
        print(f"\nrecovery: pause_max="
              f"{snap.get('pause_per_shard_us_max', 0):.0f}us "
              f"restore={recovery.get('resume', {}).get('restore_ms', 0):.1f}ms "
              f"reconnects/client="
              f"{recovery.get('reconnect', {}).get('mean_reconnects')}")
        rs = recovery.get("reshard")
        if rs is not None:
            print(f"reshard: {rs.get('from_shards')} -> "
                  f"{rs.get('to_shards')} shards "
                  f"migration={rs.get('migration_ms', 0):.1f}ms "
                  f"pause_max={rs.get('pause_per_shard_us_max', 0):.0f}us "
                  f"parked={rs.get('parked')} "
                  f"replayed={rs.get('replayed')} "
                  f"lost={rs.get('lost')}")
        failures += check_recovery(recovery, recovery_prev)
    serving = _load(args.serving, "serving")
    if serving is not None:
        serving_prev = _load(args.serving_previous, "serving-previous")
        sv = serving.get("serve", {})
        print(f"\nserving: requests={sv.get('requests')} "
              f"violations={sv.get('violations')} "
              f"p99={sv.get('p99_ms', 0):.1f}ms "
              f"rps={sv.get('requests_per_s', 0):.1f} "
              f"versions=[{sv.get('version_min')}, "
              f"{sv.get('version_max')}]")
        failures += check_serving(serving, serving_prev)
    kernels = _load(args.kernels, "kernels")
    if kernels is not None:
        kernels_prev = _load(args.kernels_previous, "kernels-previous")
        print(f"\nkernels ({kernels.get('backend')}):")
        for r in kernels.get("rows", []):
            print(f"  {r['op']:>18} {r['variant']:>16}  "
                  f"achieved {r.get('achieved_ms', 0):8.3f}ms  "
                  f"predicted {r.get('predicted_ms', 0):8.4f}ms  "
                  f"parity {r.get('parity_max_err', float('nan')):.2e}")
        failures += check_kernels(kernels, kernels_prev)
    obs = _load(args.obs, "obs")
    if obs is not None:
        obs_prev = _load(args.obs_previous, "obs-previous")
        print(f"\nobs: disabled_instant="
              f"{obs.get('disabled_ns_per_call', 0):.0f}ns/call "
              f"events_off={obs.get('events_recorded_off')} "
              f"hotpath_identical={obs.get('hotpath', {}).get('identical')}")
        failures += check_obs(obs, obs_prev)
    if failures:
        print("\nPERF GATE FAILED:")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print("\nperf gate ok"
          + (" (vs previous artifact)" if previous else
             " (no previous artifact; absolute contract only)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
