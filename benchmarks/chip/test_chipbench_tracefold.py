"""The reduction from trace events to the per-layer numbers: on events
worked out by hand, and on a small trace recorded on the chip
(``testdata/trace_excerpt.json``, 88 ms of a traced ``danube-ps.solo``
window: one push's four server applies, the pull, the next step's first
flash-attention calls)."""

from pathlib import Path

import pytest

from chip import peaks, run, tracefold

DEV = "/device:TPU:0"
OPS = tracefold.DEVICE_OPS_LINE
EXCERPT = Path(__file__).resolve().parent / "testdata" / "trace_excerpt.json"


def _op(name, ts, dur, plane=DEV):
    return {"plane": plane, "line": OPS, "name": name, "ts": ts,
            "dur": dur}


HAND = [
    {"plane": tracefold.HOST_PLANE, "line": "python",
     "name": tracefold.ANCHOR, "ts": 0.0, "dur": 1.0},
    _op("fusion.1", 10.0, 20.0),          # busy 10-30
    _op("_flash_kernel", 25.0, 15.0),     # overlaps: busy 10-40
    _op("fusion.2", 60.0, 10.0),          # busy 60-70
    _op("fusion.3", 95.0, 20.0),          # clipped at the window's end
    {"plane": DEV, "line": "XLA Modules", "name": "jit_step", "ts": 0.0,
     "dur": 200.0},                       # not an op line: ignored
]
WINDOW = (0.0, 100.0)


def test_busy_union_and_gaps_by_hand():
    ops = tracefold.device_ops(HAND, WINDOW)
    assert [n for n, _, _ in ops[DEV]] == ["fusion.1", "_flash_kernel",
                                          "fusion.2", "fusion.3"]
    # busy 10-40, 60-70, 95-100 = 45 ns of a 100 ns window
    assert tracefold.busy_seconds(ops, 1) == pytest.approx(45e-9)
    assert sorted(tracefold.gaps(ops, WINDOW)) == [
        (0.0, 10.0), (40.0, 60.0), (70.0, 95.0)]
    assert tracefold.gaps(ops, WINDOW)[0] == (70.0, 95.0)   # longest first
    assert tracefold.matching(ops, r"_flash_kernel") == (1, 15e-9)
    assert tracefold.anchor_ns(HAND) == 0.0


def test_gaps_are_named_by_the_host_span_covering_them():
    spans = [{"name": "gate_wait", "ts": 72.0, "dur": 20.0},
             {"name": "pull", "ts": 90.0, "dur": 3.0}]
    ops = tracefold.device_ops(HAND, WINDOW)
    b = tracefold.breakdown(ops, WINDOW, spans)
    assert b["idle_gaps"][0] == ["gate_wait+pull", pytest.approx(25e-9)]
    assert b["idle_gaps"][1][0] == "host:none"
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]


def test_spans_move_onto_the_profiler_clock():
    # a span drained at wall time 1000.5 s whose perf counter was 7.0 s
    # when the annotation at profiler time 5e9 ns was made
    out = tracefold.spans_on_profile_clock(
        [{"name": "push", "ts": 1000.75, "dur": 0.01}], anchor_pc=7.0,
        anchor_ts=1000.5, anchor_profile_ns=5e9)
    assert out == [{"name": "push", "ts": pytest.approx(5.25e9),
                    "dur": pytest.approx(1e7)}]


@pytest.fixture(scope="module")
def recorded():
    events = tracefold.load_excerpt(str(EXCERPT))
    ops = tracefold.device_ops(events, (-float("inf"), float("inf")))
    lo = min(s for plane in ops.values() for _, s, _ in plane)
    hi = max(t for plane in ops.values() for _, _, t in plane)
    return events, tracefold.device_ops(events, (lo, hi)), (lo, hi)


def test_recorded_trace_reduces(recorded):
    events, ops, window = recorded
    assert tracefold.anchor_ns(events) > 0
    busy = tracefold.busy_seconds(ops, 1)
    idle = sum(t - s for s, t in tracefold.gaps(ops, window)) / 1e9
    assert busy > 0
    assert busy + idle == pytest.approx((window[1] - window[0]) / 1e9)
    b = tracefold.breakdown(ops, window, [])
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("metric", ["attn_fwd_roofline", "apply_roofline"])
def test_kernel_readers_find_their_kernels(recorded, metric):
    """The kernel names the readers match are the ones the chip's trace
    shows, and the shares they give are shares (at most 100%)."""
    _, ops, window = recorded
    from chip.conftest import ROOT
    _, work, cfg, traffic, _ = run.load_cell(ROOT, "danube-ps.solo")
    from chip import cells
    family = cells.family_of(cfg)
    ctx = {"ops": ops, "engine": "ps-threads", "config": cfg,
           "model": family.sizes(cfg), "family": family,
           "traffic": traffic, "peak": peaks.peak("TPU v5 lite")}
    got = run.load_reader(metric)(ctx)
    assert got is not None, f"{metric} found no kernel in the trace"
    assert 0 < got["value"] <= 100 and got["bound"] in ("compute",
                                                        "memory")
    # the excerpt holds 2 flash-attention calls and one push's 4 applies
    assert got["calls"] == {"attn_fwd_roofline": 2,
                            "apply_roofline": 4}[metric]


def test_loop_containers_are_not_counted_twice():
    body = [_op("%fusion.1 = bf16[8] fusion(x)", 10.0, 5.0),
            _op("%fusion.2 = bf16[8] fusion(x)", 15.0, 5.0)]
    loop = _op("%while.3 = (s32[]) while(x)", 10.0, 10.0)
    ops = tracefold.device_ops(body + [loop], WINDOW)
    assert sum(tracefold.op_seconds(ops).values()) == pytest.approx(10e-9)
    assert tracefold.busy_seconds(ops, 1) == pytest.approx(10e-9)
    assert tracefold.label(
        '%closed_call.34 = bf16[1,32,2048,80]{3,2,1,0} custom-call(a, b),'
        ' custom_call_target="tpu_custom_call"') == \
        "%closed_call.34 bf16[1,32,2048,80] tpu_custom_call"
