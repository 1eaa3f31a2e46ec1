"""Run-wide tracing & telemetry (``repro.obs``) + the metric fixes
that rode along.

Covers the observability acceptance surface:

* recorder: ring bound, drain semantics, wall-clock anchoring,
  disabled calls are no-ops that record nothing;
* zero-overhead contract: perfcount deltas on the packed frame codec
  are bitwise identical with tracing off and on;
* collector: (src, seq) dedup makes frame + spill double-delivery
  idempotent; ``by_worker_clock`` ordering is stable under arrival
  order; truncated spill files (killed worker) recover cleanly;
* export: Chrome trace_event JSON loads as valid JSON and round-trips
  every native field; JSONL round-trips;
* e2e over tcp AND shmem: spawned workers' ``compute_step`` spans
  arrive at the server-side collector, the DSSP decision timeline is
  present, and ``summarize`` agrees with ``session.metrics()``;
* killed-worker path: spill files written with no collector attached
  are recovered by ``ingest_spill_dir``;
* DSSP: threshold-extension trace events == the policy's
  credit-release count;
* ``ps.metrics``: ``hist_percentile`` is bit-identical to the old
  ``statistics.quantiles`` materialization and O(distinct values);
  trajectories stay bounded with endpoints preserved;
* ``perfcount.snapshot_all`` feeds both session.metrics and the
  sampler from one base-class implementation.
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np
import pytest

from repro.obs import (
    MetricsSampler,
    TraceCollector,
    read_jsonl,
    read_trace,
    summarize,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.trace import TRACE, TraceRecorder


@pytest.fixture(autouse=True)
def _trace_off():
    """Every test starts and ends with the global recorder disabled."""
    TRACE.disable()
    yield
    TRACE.disable()


# ================================================================ recorder
def test_recorder_basic_span_and_instant():
    r = TraceRecorder()
    r.enable(source="t0")
    t0 = r.now()
    time.sleep(0.002)
    r.span("compute_step", t0, worker=3, clock=7, args={"loss": 1.5})
    r.instant("dssp_decision", worker=1, args={"reason": "free"})
    events = r.drain()
    assert [e["name"] for e in events] == ["compute_step", "dssp_decision"]
    span, inst = events
    assert span["worker"] == 3 and span["clock"] == 7
    assert span["dur"] >= 0.002 and span["args"] == {"loss": 1.5}
    assert inst["dur"] == 0.0 and inst["args"]["reason"] == "free"
    assert span["src"] == inst["src"] == "t0"
    assert inst["seq"] > span["seq"]
    # ts is anchored to wall clock, not the raw perf_counter basis
    assert abs(span["ts"] - time.time()) < 60.0
    assert r.drain() == []


def test_recorder_ring_is_bounded():
    r = TraceRecorder()
    r.enable(source="t", capacity=64)
    for i in range(1000):
        r.instant("push", clock=i)
    events = r.drain()
    assert len(events) == 64
    # oldest dropped, newest kept
    assert [e["clock"] for e in events] == list(range(936, 1000))


def test_disabled_recorder_records_nothing():
    r = TraceRecorder()
    r.instant("push")
    r.span("pull", r.now())
    assert len(r) == 0 and r.drain() == []
    r.enable(source="t")
    r.disable()
    r.instant("push")
    assert r.drain() == []


def test_enable_resets_seq_and_ring():
    r = TraceRecorder()
    r.enable(source="a")
    r.instant("push")
    r.enable(source="b")
    r.instant("push")
    (e,) = r.drain()
    assert e["seq"] == 0 and e["src"] == "b"


# ============================================= zero-overhead contract
def test_tracing_off_perfcount_deltas_bitwise_identical():
    """The packed frame codec must count exactly the same work whether
    the recorder is enabled or not (the instrumentation is read-only
    observation, never counted hot-path events)."""
    from repro.perfcount import snapshot_all
    from repro.wireformat import MSG_PUSH, Frame, decode_frame, encode_frame

    payload = np.random.RandomState(0).randn(8, 512).astype(np.float32)

    def run_once():
        before = snapshot_all()
        for clock in range(20):
            data = encode_frame(Frame(kind=MSG_PUSH, worker=1,
                                      clock=clock, payload=payload))
            decode_frame(data)
        after = snapshot_all()
        return {g: {k: after[g][k] - before[g][k] for k in after[g]}
                for g in after}

    TRACE.disable()
    off = run_once()
    assert len(TRACE) == 0  # nothing recorded while disabled
    TRACE.enable(source="test")
    on = run_once()
    assert len(TRACE.drain()) > 0  # the same path DID trace when armed
    TRACE.disable()
    assert off == on


def test_trace_frames_not_self_counted():
    """MSG_TRACE frames must not emit frame_tx/frame_rx events — a
    flush that traced itself would amplify forever."""
    from repro.wireformat import MSG_PUSH, MSG_TRACE, Frame, decode_frame, \
        encode_frame

    TRACE.enable(source="test")
    blob = json.dumps([{"seq": 0, "name": "push", "ts": 0.0}]).encode()
    decode_frame(encode_frame(Frame(kind=MSG_TRACE, worker=0, blob=blob)))
    names = {e["name"] for e in TRACE.drain()}
    assert "frame_tx" not in names and "frame_rx" not in names
    payload = np.zeros((2, 512), dtype=np.float32)
    decode_frame(encode_frame(Frame(kind=MSG_PUSH, worker=0,
                                    payload=payload)))
    names = [e["name"] for e in TRACE.drain()]
    assert names.count("frame_tx") == 1 and names.count("frame_rx") == 1


# ================================================================ collector
def _evt(seq, name="push", *, src=None, worker=-1, clock=-1, ts=0.0,
         **args):
    e = {"seq": seq, "ts": ts, "dur": 0.0, "name": name,
         "worker": worker, "shard": -1, "clock": clock}
    if src is not None:
        e["src"] = src
    if args:
        e["args"] = args
    return e


def test_collector_dedups_by_src_seq():
    c = TraceCollector()
    batch = [_evt(0, src="w0"), _evt(1, src="w0")]
    assert c.ingest("w0", batch) == 2
    # same events again (spill + frame double delivery)
    assert c.ingest("w0", [dict(e) for e in batch]) == 0
    # same seq, different src is a different event
    assert c.ingest("w1", [_evt(0, src="w1")]) == 1
    assert len(c) == 3


def test_collector_drops_malformed_and_stamps_source():
    c = TraceCollector()
    added = c.ingest("w2", [{"seq": 0, "ts": 1.0, "name": "push"},
                            "not-a-dict", {"seq": 1, "ts": 2.0}, None])
    assert added == 1
    (e,) = c.events()
    assert e["src"] == "w2"


def test_collector_by_worker_clock_stable_under_arrival_order():
    a = [_evt(0, "compute_step", src="w1", worker=1, clock=0, ts=5.0),
         _evt(1, "compute_step", src="w1", worker=1, clock=1, ts=6.0)]
    b = [_evt(0, "compute_step", src="w0", worker=0, clock=0, ts=5.5),
         _evt(1, "compute_step", src="w0", worker=0, clock=1, ts=6.5)]
    srv = [_evt(0, "apply", src="server", worker=0, clock=0, ts=5.6)]

    c1, c2 = TraceCollector(), TraceCollector()
    for batch in (a, b, srv):
        c1.ingest("x", [dict(e) for e in batch])
    for batch in (srv, b, a):
        c2.ingest("x", [dict(e) for e in batch])
    key = [(e["worker"], e["clock"], e["ts"], e["src"], e["seq"])
           for e in c1.by_worker_clock()]
    assert key == [(e["worker"], e["clock"], e["ts"], e["src"], e["seq"])
                   for e in c2.by_worker_clock()]
    assert key == sorted(key)


def test_spill_recovery_tolerates_truncated_line(tmp_path):
    """A killed worker leaves a half-written final JSONL line; recovery
    must keep every complete line and dedup against frame delivery."""
    spill = tmp_path / "spill"
    spill.mkdir()
    lines = [json.dumps(_evt(i, src="w0", worker=0, clock=i))
             for i in range(3)]
    (spill / "w0.jsonl").write_text(
        "\n".join(lines) + "\n" + lines[0][: len(lines[0]) // 2])
    c = TraceCollector()
    # events 0-1 already arrived over a TRACE frame before the kill
    c.ingest("w0", [_evt(0, src="w0", worker=0, clock=0),
                    _evt(1, src="w0", worker=0, clock=1)])
    assert c.ingest_spill_dir(spill) == 1  # only clock=2 is new
    clocks = sorted(e["clock"] for e in c.events())
    assert clocks == [0, 1, 2]


def test_metrics_sampler_samples_and_stops():
    r = TraceRecorder()
    r.enable(source="srv")
    calls = []
    s = MetricsSampler(r, lambda: calls.append(1) or {"n": len(calls)},
                       every=0.01)
    s.start()
    time.sleep(0.08)
    s.stop()
    assert not s.is_alive()
    snaps = [e for e in r.drain() if e["name"] == "metrics_snapshot"]
    assert len(snaps) >= 2  # several periodic + the final one
    assert snaps[-1]["args"]["n"] == len(calls)
    with pytest.raises(ValueError):
        MetricsSampler(r, dict, every=0.0)


# ================================================================== export
def test_chrome_trace_roundtrip(tmp_path):
    events = [
        _evt(0, "compute_step", src="w0", worker=0, clock=2, ts=10.0,
             loss=2.5),
        _evt(1, "dssp_decision", src="server", worker=1, clock=3,
             ts=10.5, reason="grant"),
    ]
    events[0]["dur"] = 0.25
    events[1]["shard"] = 1
    path = tmp_path / "trace.json"
    write_chrome_trace(events, path)

    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    phases = {r["ph"] for r in doc["traceEvents"]}
    assert {"M", "X", "i"} <= phases  # metadata + span + instant

    back = read_trace(path)
    by_seq = {e["seq"]: e for e in back}
    assert by_seq[0]["name"] == "compute_step"
    assert by_seq[0]["worker"] == 0 and by_seq[0]["clock"] == 2
    assert abs(by_seq[0]["ts"] - 10.0) < 1e-6
    assert abs(by_seq[0]["dur"] - 0.25) < 1e-6
    assert by_seq[0]["args"]["loss"] == 2.5
    assert by_seq[1]["src"] == "server" and by_seq[1]["shard"] == 1


def test_jsonl_roundtrip_and_sniffing(tmp_path):
    events = [_evt(i, src="w0", ts=float(i)) for i in range(5)]
    path = tmp_path / "trace.jsonl"
    assert write_jsonl(events, path) == 5
    assert read_jsonl(path) == events
    assert read_trace(path) == events  # sniffed as JSONL
    assert read_jsonl(tmp_path / "missing.jsonl") == []


def test_summarize_empty_and_basic():
    assert summarize([])["events"] == 0
    ev = [_evt(0, "compute_step", src="w0", worker=0, ts=0.0),
          _evt(1, "gate_wait", src="w0", worker=0, ts=1.0)]
    ev[0]["dur"] = 1.0
    ev[1]["dur"] = 0.5
    s = summarize(ev)
    assert s["workers"] == [0]
    assert s["busy_s"] == 1.0 and s["wait_s"] == 0.5
    assert s["wall_s"] == pytest.approx(1.5)
    assert s["wait_fraction"] == pytest.approx(0.5 / 1.5)


def test_summarize_dedups_extensions_across_shards():
    """One push through S shards emits S decision events with the same
    (worker, clock); RunMetrics counts the push once, so must we."""
    ev = []
    for shard_seq in range(2):  # two shards, same push
        ev.append(_evt(shard_seq, "dssp_decision", src="server",
                       worker=0, clock=5, reason="grant", threshold=3))
    ev.append(_evt(2, "dssp_decision", src="server", worker=1, clock=5,
                   reason="block", threshold=1))
    d = summarize(ev)["dssp"]
    assert d["decisions"] == 3
    assert d["threshold_extensions"] == 1


# ===================================================== DSSP decision events
def test_dssp_extension_events_match_credit_releases():
    """Drive the Algorithm-1/2 policy directly: the number of traced
    grant/credit_spend decisions equals the number of pushes released
    with ``credit_used=True`` (what RunMetrics counts)."""
    from repro.core.policies import make_policy_factory
    from repro.core.staleness import StalenessTracker

    policy = make_policy_factory("dssp", n_workers=2, staleness=1,
                                 s_lower=1, s_upper=4)()
    tracker = StalenessTracker(range(2))
    TRACE.enable(source="server")
    credit_releases = 0
    # Warm Algorithm 2's estimator first: the controller returns 0 until
    # both the fast and the slow worker have a measured push interval
    # (two pushes each), so worker 1 (slow, 10s/iter) goes first ...
    for t in (0.0, 10.0):
        tracker.record_push(1, t)
        dec = policy.on_push(tracker, 1, t)
        credit_releases += bool(dec.credit_used)
    # ... then worker 0 sprints at 1s/iter: free passes while
    # gap <= s_L, a controller grant (slow interval is 10x the fast
    # one, so r* > 0) with credit spends up to the hard bound s_U,
    # then blocks once the credits run out.
    t = 10.0
    for _ in range(10):
        t += 1.0
        tracker.record_push(0, t)
        dec = policy.on_push(tracker, 0, t)
        credit_releases += bool(dec.credit_used)
    events = TRACE.drain()
    decisions = [e for e in events if e["name"] == "dssp_decision"]
    extensions = [e for e in decisions
                  if e["args"]["reason"] in ("grant", "credit_spend")]
    assert decisions, "DSSP gate emitted no decision events"
    assert credit_releases > 0, "pattern produced no extensions"
    assert len(extensions) == credit_releases
    for e in decisions:
        a = e["args"]
        assert a["s_lower"] == 1 and a["s_upper"] == 4
        assert a["threshold"] >= a["s_lower"]
        assert e["worker"] in (0, 1) and e["clock"] >= 1


# ====================================================== e2e over transports
def _traced_spec(transport: str, trace_path: str, workers: int = 2):
    from repro import api

    return api.RunSpec(
        model=api.ModelSpec(arch="xlstm-125m"),
        data=api.DataSpec(seq_len=16, global_batch=4),
        sync=api.SyncSpec(mode="dssp", staleness=1, s_lower=1, s_upper=3),
        ps=api.ServerSpec(kind="sharded", shards=2, workers=workers,
                          apply="fused", straggler=2.0),
        wire=api.WireSpec(format="packed"),
        transport=api.TransportSpec(kind=transport),
        obs=api.ObsSpec(trace=True, trace_path=trace_path))


@pytest.mark.parametrize("transport", ["tcp", "shmem"])
def test_traced_run_collects_all_workers(transport, tmp_path):
    from repro import api

    trace_path = str(tmp_path / "run.json")
    spec = _traced_spec(transport, trace_path)
    with api.build_session(spec) as session:
        m = session.run(6)
    obs = m["obs"]
    # every worker's compute spans crossed the process boundary
    assert obs["workers"] == [0, 1]
    assert obs["event_counts"].get("compute_step", 0) >= 6
    assert obs["event_counts"].get("push", 0) >= 6
    assert obs["event_counts"].get("dssp_decision", 0) >= 1
    assert obs["dssp"]["threshold_extensions"] == m["credit_releases"]
    # session metrics carry the satellite enrichments
    assert "wait_fraction" in m and "perfcount" in m
    assert set(m["perfcount"]) == {"wire", "transport", "kernels", "moe"}

    # the exported file is valid Chrome JSON and summarizes identically
    with open(trace_path) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    back = summarize(read_trace(trace_path))
    assert back["event_counts"] == obs["event_counts"]
    assert back["dssp"]["threshold_extensions"] == \
        obs["dssp"]["threshold_extensions"]

    # merge ordering contract: worker events arrive in clock order
    events = read_trace(trace_path)
    for w in (0, 1):
        clocks = [e["clock"] for e in sorted(
            events, key=lambda e: (e.get("worker", -1),
                                   e.get("clock", -1),
                                   e.get("ts", 0.0)))
            if e.get("name") == "compute_step" and e.get("worker") == w]
        assert clocks == sorted(clocks)


def test_traced_threaded_run_and_disabled_run(tmp_path):
    """ps-threads engine: in-heap workers trace through the same global
    recorder; with obs.trace=false nothing is recorded at all."""
    from repro import api

    trace_path = str(tmp_path / "threads.jsonl")
    spec = api.RunSpec(
        model=api.ModelSpec(arch="xlstm-125m"),
        data=api.DataSpec(seq_len=16, global_batch=4),
        sync=api.SyncSpec(mode="dssp", staleness=1, s_lower=1, s_upper=3),
        ps=api.ServerSpec(kind="sharded", shards=1, workers=2),
        obs=api.ObsSpec(trace=True, trace_path=trace_path))
    with api.build_session(spec) as session:
        m = session.run(6)
    obs = m["obs"]
    assert obs["event_counts"].get("compute_step", 0) >= 6
    assert obs["dssp"]["threshold_extensions"] == m["credit_releases"]
    assert read_jsonl(trace_path)  # .jsonl path exports JSONL

    # tracing off: same run shape, no recorder, no obs key
    spec_off = api.RunSpec(
        model=spec.model, data=spec.data, sync=spec.sync, ps=spec.ps,
        wire=spec.wire)
    with api.build_session(spec_off) as session:
        m_off = session.run(6)
    assert "obs" not in m_off
    assert len(TRACE) == 0


def test_killed_worker_spill_recovered_without_collector(tmp_path):
    """Workers flushing every iteration against an endpoint with NO
    collector (frames acknowledged and dropped): the JSONL spill is the
    only surviving copy, and ``ingest_spill_dir`` recovers it — the
    abnormal-exit path, minus the nondeterministic kill."""
    from repro import api
    from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                        raise_on_failure)

    spec = _traced_spec("tcp", "", workers=2)
    session = api.build_session(spec, external_workers=True).start()
    assert session.endpoint.collector is not None
    session.endpoint.collector = None  # simulate a collector-less server
    spill = str(tmp_path / "spill")
    try:
        task = WorkerTask.from_spec(spec, 3, trace_spill=spill,
                                    trace_flush_every=1)
        pool = ProcessWorkerPool(session.transport.address(), task, 2)
        pool.start()
        results = pool.join(timeout=600.0, endpoint=session.endpoint)
        raise_on_failure(results)
    finally:
        session.close()

    c = TraceCollector()
    assert c.ingest_spill_dir(spill) > 0
    by_worker = {}
    for e in c.events():
        if e["name"] == "compute_step":
            by_worker.setdefault(e["worker"], []).append(e["clock"])
    assert sorted(by_worker) == [0, 1]
    for clocks in by_worker.values():
        assert sorted(clocks) == list(range(3))


# ======================================================= ps.metrics fixes
def test_hist_percentile_matches_statistics_reference():
    """Bit-identical to the old materialize-then-statistics.quantiles
    path, across random histograms and the old index-clamping rule."""
    from repro.ps.metrics import hist_percentile

    rng = np.random.RandomState(42)
    for _ in range(200):
        n_vals = rng.randint(1, 8)
        hist = {int(v): int(c) for v, c in zip(
            rng.choice(50, size=n_vals, replace=False),
            rng.randint(1, 30, size=n_vals))}
        xs = sorted(s for s, c in hist.items() for _ in range(c))
        for q in (0.0, 0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
            if len(xs) == 1:
                expected = float(xs[0])
            else:
                qq = statistics.quantiles(xs, n=100)
                expected = qq[min(98, max(0, int(q * 100) - 1))]
            got = hist_percentile(hist, q)
            assert got == expected, (hist, q, got, expected)


def test_hist_percentile_degenerate_and_large_counts():
    from repro.ps.metrics import hist_percentile

    assert hist_percentile({}, 0.5) == 0.0
    assert hist_percentile({7: 1}, 0.99) == 7.0
    assert hist_percentile({3: 0, 7: 1}, 0.5) == 7.0

    # tens of millions of observations: must be O(distinct values),
    # never one list entry per observation
    hist = {s: 10_000_000 for s in range(5)}
    t0 = time.perf_counter()
    p99 = hist_percentile(hist, 0.99)
    elapsed = time.perf_counter() - t0
    assert p99 == 4.0
    assert elapsed < 0.01, f"took {elapsed * 1e3:.1f}ms — materializing?"


def test_staleness_percentile_over_runmetrics():
    from repro.ps.metrics import RunMetrics, staleness_percentile

    m = RunMetrics(policy="x", n_workers=2)
    for s in (0, 0, 1, 1, 1, 2, 5):
        m.record_push(0, s, applied=True, credit=False, time=0.0)
    xs = sorted([0, 0, 1, 1, 1, 2, 5])
    qq = statistics.quantiles(xs, n=100)
    assert staleness_percentile(m, 0.5) == qq[49]
    assert staleness_percentile(m, 0.99) == qq[98]


def test_trajectories_bounded_with_endpoints_preserved():
    from repro.ps.metrics import TRAJECTORY_CAP, RunMetrics

    m = RunMetrics(policy="x", n_workers=1)
    n = TRAJECTORY_CAP * 4
    for i in range(n):
        m.record_push(0, 0, applied=True, credit=False, time=float(i))
        m.record_loss_point(float(i), i, 100.0 - i * 0.001)
    assert len(m.update_trajectory) < TRAJECTORY_CAP
    assert len(m.loss_trajectory) < TRAJECTORY_CAP
    # endpoints survive decimation (readers use [0] and [-1])
    assert m.update_trajectory[0] == (0.0, 1)
    assert m.update_trajectory[-1] == (float(n - 1), n)
    assert m.loss_trajectory[0][2] == 100.0
    assert m.loss_trajectory[-1][2] == pytest.approx(100.0 - (n - 1) * 0.001)
    # time_to_* remain exact at the recorded resolution
    assert m.time_to_updates(n) == float(n - 1)
    assert m.time_to_loss(100.0 - (n - 1) * 0.001) == float(n - 1)
    assert m.time_to_updates(n + 1) is None


def test_perfcount_snapshot_all_and_base_class():
    from repro.perfcount import TRANSPORT, WIRE, snapshot_all

    WIRE.reset()
    TRANSPORT.reset()
    snap = snapshot_all()
    assert set(snap) == {"wire", "transport", "kernels", "moe"}
    assert snap["wire"]["pallas_calls"] == 0
    WIRE.pallas_calls += 3
    TRANSPORT.frames_tx += 2
    before = snapshot_all()
    WIRE.pallas_calls += 1
    d = WIRE.delta(before["wire"])
    assert d["pallas_calls"] == 1
    assert all(v == 0 for k, v in d.items() if k != "pallas_calls")
    assert snapshot_all()["transport"]["frames_tx"] == 2


# ============================================================== CLI
def test_obs_cli_summarize(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main

    ev = [_evt(0, "compute_step", src="w0", worker=0, clock=0, ts=1.0)]
    ev[0]["dur"] = 0.5
    path = str(tmp_path / "t.jsonl")
    write_jsonl(ev, path)
    assert obs_main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "events" in out and "wall time" in out
    assert obs_main(["summarize", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["events"] == 1
    assert obs_main(["summarize", str(tmp_path / "missing.json")]) != 0
