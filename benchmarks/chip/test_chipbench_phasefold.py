"""The fold of device time by program phase, and the readers on it: on
op paths and ops worked out by hand, and on a trace recorded on the chip
with each op's path (``testdata/trace_excerpt_phases.json``)."""

import json
import sys
from pathlib import Path

import pytest

from chip import phasefold, run, tracefold

DEV = "/device:TPU:0"
OPS = tracefold.DEVICE_OPS_LINE
STEP = "jit(_packed_step)"
BODY = f"{STEP}/transpose(jvp())/while/body/closed_call/checkpoint"


def _phases():
    from repro.obs.trace import FOLD_PHASES, PHASE_PROGRAMS, PHASES
    return PHASES, PHASE_PROGRAMS, FOLD_PHASES


@pytest.mark.parametrize("path,phase", [
    (f"{STEP}/wire/slice", "wire"),
    (f"{STEP}/wire/reshape", "wire"),
    (f"{BODY}/bld,dhk->blhk/dot_general", "bwd"),
    (f"{BODY}/rematted_computation/bld,dhk->blhk/dot_general", "fwd"),
    (f"{STEP}/jvp()/while/body/closed_call/attn_fwd/transpose", "attn_fwd"),
    (f"{STEP}/jvp()/while/body/closed_call/attn_fwd/flash_attention_fwd/"
     "pallas_call", "attn_fwd"),
    (f"{BODY}/rematted_computation/attn_fwd/flash_attention_fwd/"
     "pallas_call", "attn_fwd"),
    (f"{BODY}/attn_fwd/attn_bwd/transpose(jvp(bhlm,bmhd->blhd))/"
     "dot_general", "attn_bwd"),
    (f"{BODY}/attn_fwd/attn_bwd/jvp()/div", "attn_bwd"),
    # the XLA attention's backward has no attn_bwd scope of its own
    (f"{BODY}/attn_fwd/dot_general", "attn_bwd"),
    ("jit(loss)/transpose(jvp(attn_fwd))/attn_bwd/jvp()/div", "attn_bwd"),
    ("jit(loss)/transpose(jvp(attn_fwd))/dot_general", "attn_bwd"),
    ("jit(_fused_update)/pallas_call", "apply"),
    ("jit(_fused_update)", "apply"),
    ("jit(apply_region)/dynamic_slice", "apply"),
    ("jit(wire_concat)/concatenate", "wire"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/jvp()/while/body/closed_call/mul", "fwd"),
    (f"{STEP}/jvp(rmsnorm)/pallas_call", "fwd"),
    (f"{STEP}/transpose(jvp(jit(take_along_axis)))/scatter-add", "bwd"),
    (STEP, "unscoped"),
    ("jit(step)/convert_element_type", "unscoped"),
    ("jit(concatenate)/concatenate", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of_a_path(path, phase):
    phases, programs, _ = _phases()
    assert phasefold.phase_of(path, phases, programs) == phase


def test_paths_split_outside_brackets():
    assert phasefold.split_path("jit(a/b)/x/transpose(jvp(c/d))/y") == \
        ["jit(a/b)", "x", "transpose(jvp(c/d))", "y"]


def _op(name, ts, dur):
    return {"plane": DEV, "line": OPS, "name": name, "ts": ts, "dur": dur}


HAND = [
    _op("%fusion.1 = bf16[8] fusion(a)", 0.0, 10.0),       # wire
    _op("%while.2 = (s32[]) while(b)", 10.0, 30.0),        # container
    _op("%fusion.3 = bf16[8] fusion(c)", 10.0, 20.0),      # bwd
    _op("%copy.4 = bf16[8] copy(d)", 30.0, 5.0),           # no path
    _op("%_fused_update.1 = (bf16[8]) custom-call(e)", 40.0, 25.0),
]
PATHS = {
    "%fusion.1 = bf16[8] fusion(a)": f"{STEP}/wire/slice",
    "%while.2 = (s32[]) while(b)": f"{STEP}/transpose(jvp())/while",
    "%fusion.3 = bf16[8] fusion(c)": f"{BODY}/mul",
    "%_fused_update.1 = (bf16[8]) custom-call(e)":
        "jit(_fused_update)/pallas_call",
}


def test_fold_by_hand():
    phases, programs, names = _phases()
    ops = tracefold.device_ops(HAND, (0.0, 100.0))
    got = phasefold.fold(ops, PATHS.get, phases, programs, names)
    assert set(got) == set(names)
    want = {"wire": 10e-9, "bwd": 20e-9, "unscoped": 5e-9, "apply": 25e-9}
    for k in names:
        assert got[k] == pytest.approx(want.get(k, 0.0)), k
    # the loop container is in the busy union, not in the fold
    assert sum(got.values()) == pytest.approx(60e-9)


def test_join_on_the_head_a_trace_name_starts_with():
    from repro.obs.device import op_head
    hlo = "  ROOT %a.1 = f32[2]{0} add(%x, %y), metadata={op_name=\"j\"}"
    # a device trace spells out the operand shapes; the head is the same
    trace = "%a.1 = f32[2]{0} add(f32[2]{0} %x, f32[2]{0} %y)"
    assert op_head(hlo) == op_head(trace) == "%a.1 = f32[2]{0}"


def _ctx(ops=None, **kw):
    ctx = {"ops": ops or {}, "breakdown": {"device_ops": [],
                                           "idle_gaps": []},
           "busy_s": 1.0, "n_chips": 1}
    ctx.update(kw)
    return ctx


def test_fold_ctx_names_the_window_from_the_program(monkeypatch):
    from repro.obs import device
    monkeypatch.setattr(device, "op_paths", lambda: {
        device.op_head(k): v for k, v in PATHS.items()})
    ctx = _ctx(tracefold.device_ops(HAND, (0.0, 100.0)))
    got = phasefold.fold_ctx(ctx)
    assert got["apply"] == pytest.approx(25e-9)
    assert ctx["breakdown"]["phases"] is got
    monkeypatch.setattr(device, "op_paths", lambda: {})
    assert phasefold.fold_ctx(ctx) is got            # once per run


def test_fold_ctx_is_none_for_a_program_without_names(monkeypatch):
    import repro.obs
    monkeypatch.delattr(repro.obs, "device", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.device", None)
    ctx = _ctx()
    assert phasefold.fold_ctx(ctx) is None
    assert "phases" not in ctx["breakdown"]
    for name in ("attn_bwd_roofline", "wire_device_share"):
        assert run.load_reader(name)(_ctx()) is None


SOLO = {"batch": 1, "seq_len": 2048}


def _danube():
    from chip import cells
    from chip.conftest import ROOT
    _, _, cfg, _, _ = run.load_cell(ROOT, "danube-ps.solo")
    return cells.family_of(cfg).sizes(cfg)


def test_attn_bwd_roofline_by_hand():
    from chip import peaks
    m = _danube()
    read = run.load_reader("attn_bwd_roofline")
    ctx = _ctx(model=m, traffic=SOLO, peak=peaks.peak("TPU v5 lite"),
               counters={"tokens": 10 * 2048, "seconds": 2.5},
               window_s=2.5, phase_seconds={"attn_bwd": 0.44})
    got = read(ctx)
    # 10 steps x 8 layers; 10 FLOPs a head_dim element of each of the
    # 2048 * 2049 / 2 causal pairs of 32 heads: 0.2727 ms a call
    assert got["calls"] == 80 and got["bound"] == "compute"
    per_call = 10 * 32 * 80 * 2048 * 2049 / 2 / 197e12
    assert got["value"] == pytest.approx(100 * 80 * per_call / 0.44)
    assert per_call == pytest.approx(2.727e-4, rel=1e-3)
    # a trace 10% longer than the counted steps holds 10% more calls
    ctx["window_s"] = 2.75
    assert read(ctx)["calls"] == pytest.approx(88)
    ctx["phase_seconds"] = {"attn_bwd": 0.0}
    assert read(ctx) is None


def test_wire_device_share_by_hand():
    read = run.load_reader("wire_device_share")
    ctx = _ctx(busy_s=2.0, n_chips=1, phase_seconds={"wire": 0.25})
    assert read(ctx) == pytest.approx(12.5)
    ctx["phase_seconds"] = {"wire": 0.0}
    assert read(ctx) is None                          # no wire: no share


def test_pull_copy_gb_over_the_traced_interval():
    from repro.obs.trace import TRACE
    from repro.perfcount import WIRE
    read = run.load_reader("pull_copy_gb")
    TRACE.enable(source="test")
    try:
        for _ in range(3):
            WIRE.pulls += 1
            WIRE.pull_assemble_bytes += 1405528 * 512 * 2
        WIRE.h2d_bytes += 0
    finally:
        TRACE.disable()
    WIRE.pull_assemble_bytes += 10**9                 # after the interval
    got = read(_ctx())
    assert got["pulls"] == 3
    assert got["value"] == pytest.approx(1.439260672)


def test_pull_copy_gb_is_none_without_the_counters(monkeypatch):
    from repro.obs import trace

    class Older:
        enabled = False

    monkeypatch.setattr(trace, "TRACE", Older())
    assert run.load_reader("pull_copy_gb")(_ctx()) is None



# ------------------------------------------------ the recorded excerpt
RECORDED = Path(__file__).resolve().parent / "testdata" / \
    "trace_excerpt_phases.json"


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)["cells"]


#: Seconds per phase of the excerpt's events, added up by hand from the
#: recorded file (the 20 longest op events of each phase).
EXCERPT_SECONDS = {
    "danube-ps.solo": {"wire": 0.087915, "attn_fwd": 0.056897,
                       "attn_bwd": 0.033604, "apply": 0.308311,
                       "fwd": 0.025923, "bwd": 0.036549},
    "danube-spmd.adamw": {"attn_fwd": 0.072824, "attn_bwd": 0.03308,
                          "optimizer": 0.094404, "fwd": 0.030251,
                          "bwd": 0.036223},
}


@pytest.mark.parametrize("cell", sorted(EXCERPT_SECONDS))
def test_recorded_paths_give_the_recorded_phases(recorded, cell):
    phases, programs, _ = _phases()
    for e in recorded[cell]["events"]:
        assert phasefold.phase_of(e["path"], phases, programs) == \
            e["phase"], e["path"]


@pytest.mark.parametrize("cell", sorted(EXCERPT_SECONDS))
def test_recorded_excerpt_folds_to_known_seconds(recorded, cell):
    phases, programs, names = _phases()
    events = recorded[cell]["events"]
    ops = tracefold.device_ops(events, (-float("inf"), float("inf")))
    paths = {e["name"]: e["path"] for e in events}
    got = phasefold.fold(ops, paths.get, phases, programs, names)
    for name in names:
        assert got[name] == pytest.approx(
            EXCERPT_SECONDS[cell].get(name, 0.0), abs=2e-6), name


@pytest.mark.parametrize("cell,present", [
    ("danube-ps.solo", ("wire", "attn_fwd", "attn_bwd", "apply", "fwd",
                        "bwd")),
    ("danube-spmd.adamw", ("attn_fwd", "attn_bwd", "optimizer", "fwd",
                           "bwd")),
])
def test_recorded_window_is_named_to_the_last_second(recorded, cell,
                                                     present):
    """The chip window's fold: its phases add up to the busy time, almost
    nothing is unscoped, and every phase the cell runs is there."""
    c = recorded[cell]
    total = sum(c["phases"].values())
    assert total == pytest.approx(c["busy_s"], rel=0.01)
    assert c["phases"]["unscoped"] < 0.05 * total
    assert all(c["phases"][p] > 0 for p in present)


def test_recorded_readers_know_their_answers(recorded):
    from chip import peaks
    c = recorded["danube-ps.solo"]
    ctx = _ctx(model=_danube(), traffic=SOLO,
               peak=peaks.peak("TPU v5 lite"), busy_s=c["busy_s"],
               window_s=c["window_s"], phase_seconds=c["phases"],
               counters={"tokens": c["tokens"], "seconds": c["window_s"]})
    bwd = run.load_reader("attn_bwd_roofline")(ctx)
    # 12 steps x 8 layers at 0.2727 ms each, over 0.7098 s of attn_bwd
    assert bwd["calls"] == c["tokens"] / 2048 * 8 == 96
    assert bwd["value"] == pytest.approx(
        100 * 96 * 2.7274e-4 / c["phases"]["attn_bwd"], rel=1e-3)
    share = run.load_reader("wire_device_share")(ctx)
    assert share == pytest.approx(
        100 * c["phases"]["wire"] / c["busy_s"])
