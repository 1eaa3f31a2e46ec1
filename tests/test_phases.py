"""Device phases and the host spans and counters around them.

* the vocabulary: the new span names, no ``kernel_launch``;
* the recorder's hooks and its counter deltas over an armed interval;
* the jitted code carries its phase scopes and every Pallas kernel its
  ``name=`` (read from ``lower(...).as_text(debug_info=True)`` at smoke
  size);
* the wire counters are exact: a ``pull_packed`` miss counts the
  layout's bytes, a hit counts nothing, a delta pull counts the host
  buffer it uploads;
* the worker and trainer spans nest inside ``compute_step``, and
  ``summarize`` counts a straggler's sleep as the compute it was.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import make_policy_factory
from repro.kernels import flash_attention as fa
from repro.kernels import fused_compress as fc
from repro.kernels import fused_update as fu
from repro.kernels import registry as kreg
from repro.kernels import residual_rmsnorm as rrn
from repro.kernels import rmsnorm as rn
from repro.kernels import ssm_scan as scan
from repro.obs import device, summarize
from repro.obs.trace import (EVENT_NAMES, FOLD_PHASES, PHASE_PROGRAMS,
                             PHASES, TRACE, TraceRecorder)
from repro.perfcount import WIRE
from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
from repro.ps.step import packed_step
from repro.ps.worker import PSWorker, run_cluster
from repro.wireformat import WIRE_LANES


@pytest.fixture(autouse=True)
def _trace_off():
    TRACE.disable()
    yield
    TRACE.disable()


def _tree(seed=0, shapes=((40, 16), (16,), (8, 8), ())):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(np.asarray(rng.randn(*s), np.float32))
            for i, s in enumerate(shapes)}


def _server(shards=3, workers=1):
    return ShardedParameterServer(
        _tree(), make_policy_factory("asp"),
        lambda: ServerOptimizer(lr=0.1), workers, shards)


def _debug_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


# ============================================================ vocabulary
@pytest.mark.parametrize("name", ["batch", "dispatch", "device_wait",
                                  "straggler_sleep", "reshard",
                                  "reshard_shard"])
def test_new_span_names_in_vocabulary(name):
    assert name in EVENT_NAMES


def test_kernel_launch_left_the_vocabulary():
    assert "kernel_launch" not in EVENT_NAMES
    assert set(PHASES) <= set(FOLD_PHASES)
    assert set(PHASE_PROGRAMS.values()) <= set(PHASES)


def test_phase_scope_refuses_unknown_names():
    with pytest.raises(ValueError, match="unknown device phase"):
        device.phase("attention")


# ============================================================ recorder
def test_hooks_see_enable_and_disable():
    r = TraceRecorder()
    seen = []
    r.add_hook(seen.append)
    r.add_hook(seen.append)          # once per distinct hook
    r.enable(source="t")
    r.disable()
    assert seen == ["enable", "disable"]


def test_window_counters_cover_the_armed_interval():
    r = TraceRecorder()
    assert r.window_counters() == {}
    WIRE.pulls += 5                  # before: not in the interval
    r.enable(source="t")
    WIRE.pulls += 2
    WIRE.h2d_bytes += 1024
    assert r.window_counters()["wire"]["pulls"] == 2     # while armed
    r.disable()
    WIRE.pulls += 7                  # after: not in the interval
    got = r.window_counters()["wire"]
    assert (got["pulls"], got["h2d_bytes"]) == (2, 1024)
    assert r.window_counters()["transport"]["frames_tx"] == 0


def test_enable_holds_the_loaded_programs_until_read():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    f(jnp.ones(4)).block_until_ready()
    TRACE.enable(source="t")
    TRACE.disable()
    paths = device.op_paths()
    assert any(p.startswith("jit(<lambda>)") for p in paths.values())
    assert device.op_paths() == {}   # released by the first read


def test_module_op_paths_keys_ops_by_their_head():
    text = (
        "HloModule jit__fused_update\n"
        "ENTRY %main.2 (p.1: bf16[8,512]) -> bf16[8,512] {\n"
        "  %p.1 = bf16[8,512]{1,0} parameter(0)\n"
        "  ROOT %_fused_update.1 = bf16[8,512]{1,0} custom-call(%p.1), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(_fused_update)/pallas_call" '
        'stack_frame_id=3}, backend_config={"flag_configs":[]}\n'
        "}\n")
    paths = device.module_op_paths(text, "jit__fused_update")
    assert paths["%_fused_update.1 = bf16[8,512]{1,0}"] == \
        "jit(_fused_update)/pallas_call"
    # no op_name of its own: the path of the op reading it
    assert paths["%p.1 = bf16[8,512]{1,0}"] == \
        "jit(_fused_update)/pallas_call"


def test_a_head_two_programs_share_is_unknown(monkeypatch):
    class Module:
        def __init__(self, name, path):
            self.name = name
            self.text = ("ENTRY %main {\n  %copy.1 = f32[]{:T(128)} "
                         f'copy(%a), metadata={{op_name="{path}"}}\n}}\n')

        def to_string(self):
            return self.text

    class Exe:
        def __init__(self, *mods):
            self.mods = mods

        def hlo_modules(self):
            return list(self.mods)

    monkeypatch.setattr(device, "_HELD", [
        Exe(Module("jit_f", "jit(f)/wire/copy")),
        Exe(Module("jit_g", "jit(g)/optimizer/copy"))])
    assert device.op_paths() == {"%copy.1 = f32[]{:T(128)}": ""}


def test_ops_xla_made_take_a_neighbour_path():
    """A layout copy or buffer update XLA made has no op_name: it takes
    its operand's path, else its reader's, else the program's."""
    text = (
        "HloModule jit_step\n"
        "ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {\n"
        "  %a = f32[8]{0} parameter(0)\n"
        "  %b = f32[8]{0} parameter(1)\n"
        "  %copy.1 = f32[8]{0} copy(%a)\n"
        "  %fusion.2 = f32[8]{0} fusion(%copy.1), kind=kLoop, "
        'calls=%f, metadata={op_name="jit(step)/wire/slice"}\n'
        "  %dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice("
        "%b, %fusion.2)\n"
        "  ROOT %tuple.4 = (f32[8]{0}) tuple(%b)\n"
        "}\n")
    paths = device.module_op_paths(text, "jit_step")
    by_name = {k.split(" ", 1)[0]: v for k, v in paths.items()}
    assert by_name["%copy.1"] == "jit(step)/wire/slice"       # its reader
    assert by_name["%dynamic-update-slice.3"] == "jit(step)/wire/slice"
    assert by_name["%tuple.4"] == "jit(step)"                  # nothing


# ============================================================ scopes
def test_packed_step_scopes_its_wire_glue():
    from repro import api
    spec = api.RunSpec(
        model=api.ModelSpec(arch="xlstm-125m"),
        data=api.DataSpec(seq_len=16, global_batch=2),
        sync=api.SyncSpec(mode="asp"),
        ps=api.ServerSpec(kind="sharded", shards=2, workers=1))
    with api.build_session(spec) as session:
        session.run(1)
        step, plan = session.worker_step, session.server.plan
        wire = plan.pack(session.server.params)
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.zeros((2, 16), jnp.int32)}
        text = step.lower(wire, wire, batch).as_text(debug_info=True)
    assert "jit(_packed_step)/wire/" in text
    assert "transpose(jvp(" in text          # the backward, told apart


def test_spmd_step_scopes_its_optimizer_and_attention():
    from repro.configs import get_smoke_config
    from repro.data.synthetic import DataConfig
    from repro.launch.train import Trainer
    cfg = get_smoke_config("h2o-danube-1.8b-chip")
    tr = Trainer(cfg, DataConfig(cfg.vocab_size, 16, 1),
                 sync="dssp", s_lower=0, s_upper=1, optimizer="adamw",
                 lr=1e-3)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "labels": jnp.zeros((1, 16), jnp.int32)}
    text = tr._jit_step.lower(tr.params, tr.opt_state, tr.pipeline,
                              tr.err_state, batch,
                              jnp.int32(0)).as_text(debug_info=True)
    assert "jit(step)/optimizer/" in text
    assert "/attn_fwd/" in text


def test_attention_backward_carries_attn_bwd():
    q = jnp.ones((1, 16, 2, 8))

    def loss(q, k, v):
        return kreg.attention(q, k, v, kernels="pallas").sum()

    text = _debug_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert "transpose(jvp(attn_fwd))/attn_bwd/" in text


_X = jnp.ones((16, 256))
_W = jnp.ones((256,))
_BUF = jnp.ones((8, WIRE_LANES))
_QKV = jnp.ones((1, 16, 2, 8))
_SCAN = (jnp.ones((1, 16, 8)), jnp.ones((1, 16, 8)), -jnp.ones((8, 4)),
         jnp.ones((1, 16, 4)), jnp.ones((1, 16, 4)), jnp.zeros((1, 8, 4)))

KERNELS = {
    "flash_attention_fwd": (functools.partial(
        fa.flash_attention_fwd, causal=True, block_q=8, block_k=8,
        interpret=True), (_QKV, _QKV, _QKV)),
    "rmsnorm": (functools.partial(rn.rmsnorm, interpret=True), (_X, _W)),
    "residual_rmsnorm": (functools.partial(
        rrn.residual_rmsnorm, interpret=True), (_X, _X, _W)),
    "ssm_scan": (functools.partial(scan.ssm_scan, chunk=8,
                                   interpret=True), _SCAN),
    "fused_momentum_apply_batched": (functools.partial(
        fu.fused_update_batched, lr=0.1, scales=(1.0, 0.5),
        interpret=True), (_BUF, _BUF, jnp.ones((2, 8, WIRE_LANES)))),
    "fused_int8_ef": (functools.partial(fc.fused_int8_ef, interpret=True),
                      (_BUF, _BUF)),
    "fused_topk_ef": (functools.partial(fc.fused_topk_ef, interpret=True),
                      (_BUF, _BUF)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_every_kernel_carries_its_name(name):
    fn, args = KERNELS[name]
    text = _debug_text(fn, *args)
    assert re.search(rf'loc\("{name}[/"]', text), name


def test_apply_kernel_keeps_its_program_name():
    """The apply kernel has no ``name=``: the benchmark's apply reader
    matches the custom call by the jitted ``_fused_update`` around it."""
    text = _debug_text(functools.partial(fu.fused_update, lr=0.1,
                                         interpret=True), _BUF, _BUF, _BUF)
    assert 'loc("_fused_update"' in text
    assert "fused_momentum_apply" not in text


# ============================================================ counters
def test_pull_packed_miss_counts_the_layout_and_a_hit_nothing():
    server = _server(shards=3)
    layout = server.plan.wire_layout()
    nbytes = layout.total_rows * WIRE_LANES * jnp.dtype(layout.dtype).itemsize
    before = WIRE.snapshot()
    w1 = server.pull_packed(0)                       # miss
    assert WIRE.delta(before)["pull_assemble_bytes"] == nbytes
    assert w1.nbytes == nbytes
    before = WIRE.snapshot()
    assert server.pull_packed(0) is w1               # hit
    assert WIRE.delta(before)["pull_assemble_bytes"] == 0


def test_one_shard_pull_assembles_nothing():
    server = _server(shards=1)
    before = WIRE.snapshot()
    server.pull_packed(0)
    assert WIRE.delta(before)["pull_assemble_bytes"] == 0


def test_push_regions_come_from_the_apply_program():
    server = _server(shards=3)
    g = server.plan.pack(_tree(seed=1))
    server.push_packed(0, g)
    for j in range(3):
        want = np.asarray(server.plan.shard_wire(g, j))
        layout = server.plan.wire_layout()
        from repro.ps.sharded.server import apply_region
        got = apply_region(g, layout.shard_row_start[j],
                           layout.shard_rows[j])
        np.testing.assert_array_equal(np.asarray(got), want)


def test_delta_pull_counts_the_buffer_it_uploads():
    server = _server(shards=3)
    worker = PSWorker(0, server, None, iter(()), 0, delta_pull=True)
    pull = worker._delta_puller()
    layout = server.plan.wire_layout()
    full = layout.total_rows * WIRE_LANES * jnp.dtype(layout.dtype).itemsize
    before = WIRE.snapshot()
    w1 = pull(0)                                     # bootstrap: every shard
    d = WIRE.delta(before)
    assert d["h2d_bytes"] == full and d["delta_bytes_tx"] == full
    before = WIRE.snapshot()
    assert pull(0) is w1                             # nothing changed
    assert WIRE.delta(before)["h2d_bytes"] == 0
    # one shard's region changes: the delta ships that region, the
    # upload is still the whole resident buffer
    st = server.shards[1]
    with st.cond:
        st.version += 1
    before = WIRE.snapshot()
    pull(0)
    d = WIRE.delta(before)
    region = layout.shard_rows[1] * WIRE_LANES * \
        jnp.dtype(layout.dtype).itemsize
    assert d["delta_bytes_tx"] == region and d["h2d_bytes"] == full


# ============================================================ spans
def _spans(events, name, worker=None):
    return [e for e in events if e["name"] == name
            and (worker is None or e["worker"] == worker)]


def _inside(child, parent, slack=1e-6):
    return (parent["ts"] - slack <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack)


def test_worker_spans_nest_and_the_sleep_is_its_own():
    server = _server(shards=2, workers=1)
    plan = server.plan

    def tree_step(p, batch):
        grads = jax.tree_util.tree_map(lambda x: x * batch, p)
        return grads, {"loss": jnp.float32(0.0)}

    batches = iter([jnp.float32(0.01)] * 4)
    TRACE.enable(source="t")
    run_cluster(server, [PSWorker(0, server, packed_step(plan, tree_step),
                                  batches, 4, speed_factor=2.0)],
                timeout=120.0)
    events = TRACE.drain()
    steps = _spans(events, "compute_step", 0)
    assert len(steps) == 4
    for parent in steps:
        kids = [e for e in events if e["clock"] == parent["clock"]
                and e["name"] in ("batch", "dispatch", "device_wait")]
        assert sorted(e["name"] for e in kids) == ["batch", "device_wait",
                                                   "dispatch"]
        assert all(_inside(k, parent) for k in kids)
        (sleep,) = [e for e in _spans(events, "straggler_sleep")
                    if e["clock"] == parent["clock"]]
        assert sleep["ts"] >= parent["ts"] + parent["dur"] - 1e-6


def test_trainer_spans_batch_then_dispatch_and_wait():
    from repro.configs import get_smoke_config
    from repro.data.synthetic import DataConfig
    from repro.launch.train import Trainer
    cfg = get_smoke_config("h2o-danube-1.8b-chip")
    tr = Trainer(cfg, DataConfig(cfg.vocab_size, 16, 1), sync="bsp",
                 optimizer="adamw", lr=1e-3)
    tr.train(1)                                      # compiles untraced
    TRACE.enable(source="t")
    tr.train(3)
    events = TRACE.drain()
    steps = _spans(events, "compute_step")
    assert [e["clock"] for e in steps] == [1, 2, 3]
    for parent in steps:
        same = {e["name"]: e for e in events
                if e["clock"] == parent["clock"]}
        assert _inside(same["dispatch"], parent)
        assert _inside(same["device_wait"], parent)
        assert same["dispatch"]["ts"] <= same["device_wait"]["ts"]
        assert same["batch"]["ts"] + same["batch"]["dur"] <= \
            parent["ts"] + 1e-6


def _evt(name, ts, dur, worker=0):
    return {"seq": int(ts * 10), "ts": ts, "dur": dur, "name": name,
            "worker": worker, "shard": -1, "clock": -1, "src": "w0"}


def test_summarize_counts_the_sleep_as_compute():
    """A straggler's step as one span (sleep inside) and as a step and
    its sleep give the same summary, but for the event counts."""
    gate = [_evt("gate_wait", 4.0, 0.5)]
    one = summarize([_evt("compute_step", 0.0, 3.0)] + gate)
    two = summarize([_evt("compute_step", 0.0, 1.0),
                     _evt("straggler_sleep", 1.0, 2.0)] + gate)
    for s in (one, two):
        s.pop("event_counts")
        s.pop("events")
    assert one == two
    assert two["busy_s"] == pytest.approx(3.0)
