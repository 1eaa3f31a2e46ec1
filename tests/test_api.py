"""The repro.api session layer: spec validation, round-trips, engine
wiring, the server protocol, deprecation shims, and the schema lock.

Covers the PR-4 acceptance surface:

* every sync paradigm x every valid (server, wire, transport)
  combination builds via ``build_session`` from a plain dict and
  round-trips ``to_dict``/``from_dict`` bitwise;
* invalid combinations raise ``SpecError`` with an actionable message,
  and the removed server kind, applies and wire format name what
  replaces them;
* legacy direct construction still works, emits a single
  ``DeprecationWarning`` naming the replacement, and is behaviorally
  identical to the api-built server;
* a process-transport run driven purely by a spec matches the
  pre-refactor manual wiring bitwise (single worker = deterministic).
"""

from __future__ import annotations

import json
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import _compat
from repro.api import (
    DataSpec,
    ModelSpec,
    OptimizerSpec,
    RunSpec,
    ServerSpec,
    SpecError,
    SyncSpec,
    TransportSpec,
    WireSpec,
    build_session,
    dump_schema,
)

SCHEMA_PATH = (pathlib.Path(__file__).parent.parent
               / "src" / "repro" / "api" / "schema.json")


# ---------------------------------------------------------------- helpers
def tiny_problem():
    rng = np.random.RandomState(0)
    x = rng.randn(256, 8).astype(np.float32)
    y = (x.sum(-1) > 0).astype(np.int32)

    def loss_fn(params, batch):
        bx, by = batch
        logp = jax.nn.log_softmax(bx @ params["w"] + params["b"])
        return -jnp.mean(jnp.take_along_axis(logp, by[:, None], 1))

    def step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads, {"loss": loss}

    def batches(w, n_workers=2, bs=32):
        sx, sy = x[w::n_workers], y[w::n_workers]
        rng = np.random.RandomState(100 + w)
        while True:
            i = rng.randint(0, len(sx), bs)
            yield sx[i], sy[i]

    params = {"w": jnp.zeros((8, 2)), "b": jnp.zeros((2,))}
    return params, step, batches


def all_valid_specs():
    """The full (sync) x (shards, transport, compression) grid."""
    combos = [
        # (shards, transport, compression)
        (1, "inproc", "none"),
        (2, "inproc", "int8"),
        (2, "inproc", "topk"),
        (1, "tcp", "none"),
        (2, "tcp", "int8"),
        (2, "shmem", "topk"),
    ]
    specs = []
    for sync in ("bsp", "ssp", "dssp"):          # spmd has no asp
        specs.append(RunSpec(sync=SyncSpec(mode=sync, s_lower=1,
                                           s_upper=3)))
    for sync in ("bsp", "asp", "ssp", "dssp"):
        for shards, tp, comp in combos:
            specs.append(RunSpec(
                sync=SyncSpec(mode=sync, staleness=2, s_lower=1,
                              s_upper=3),
                ps=ServerSpec(kind="sharded", shards=shards, workers=2),
                wire=WireSpec(compression=comp),
                transport=TransportSpec(kind=tp)))
    return specs


# ============================================================ spec layer
def test_every_valid_combo_builds_and_roundtrips():
    for spec in all_valid_specs():
        d = spec.to_dict()
        # bitwise dict round-trip, through JSON
        again = RunSpec.from_dict(json.loads(json.dumps(d)))
        assert again == spec
        assert again.to_dict() == d
        # and the dict form builds a session of the right engine
        session = build_session(d)
        assert session.engine == spec.engine
        assert not session._started   # building is lazy — no server yet


def test_engine_selection():
    assert RunSpec().engine == "spmd"
    assert RunSpec(ps=ServerSpec(kind="sharded", shards=1)).engine == \
        "ps-threads"
    assert RunSpec(ps=ServerSpec(kind="sharded", shards=2),
                   transport=TransportSpec(kind="tcp")).engine == \
        "ps-transport"


@pytest.mark.parametrize("mutate,needle", [
    (dict(sync=dict(mode="asp")), "SPMD"),
    (dict(transport=dict(kind="tcp")), "ps.kind='sharded'"),
    (dict(ps=dict(gating="global")), "gating"),
    (dict(ps=dict(kind="sharded", shards=0)), "shards"),
    (dict(ps=dict(kind="none", shards=2)), "shards=0"),
    (dict(sync=dict(mode="hogwild")), "sync.mode"),
    (dict(sync=dict(s_lower=5, s_upper=2)), "s_lower"),
    (dict(model=dict(arch="not-a-real-arch")), "architecture"),
    (dict(ps=dict(kind="sharded", shards=2),
          optimizer=dict(name="adamw")), "SGD/momentum"),
    (dict(optimizer=dict(lr=-1.0)), "lr"),
    # PR-5 knobs: delta pulls and coalescing ride the packed wire only
    (dict(wire=dict(delta_pull=True)), "delta_pull"),
    (dict(ps=dict(coalesce=2)), "coalesce"),
    (dict(ps=dict(kind="sharded", shards=2, coalesce=0)), "window"),
    (dict(ps=dict(kind="sharded", shards=2, coalesce_wait_ms=-5.0)),
     "coalesce_wait_ms"),
    # PR-7 knobs: the ft block needs a PS, a packed store, a faultable
    # transport, and a restartable (tcp) one for server kills
    (dict(ft=dict(snapshot_every_s=1.0, dir="/tmp/ck")),
     "parameter server"),
    (dict(ps=dict(kind="sharded", shards=2, apply="fused"),
          wire=dict(format="packed"),
          ft=dict(fault_drop_prob=0.1)), "transport.kind"),
    (dict(ps=dict(kind="sharded", shards=2, apply="fused"),
          wire=dict(format="packed"), transport=dict(kind="shmem"),
          ft=dict(fault_kill_server_round=5)), "tcp"),
    (dict(ps=dict(kind="sharded", shards=2, apply="fused"),
          wire=dict(format="packed"), transport=dict(kind="shmem"),
          ft=dict(reconnect_tries=3)), "tcp"),
    (dict(ft=dict(keep=0)), "keep"),
    (dict(ps=dict(kind="sharded", shards=2, apply="fused"),
          wire=dict(format="packed"), transport=dict(kind="tcp"),
          ft=dict(snapshot_every_s=1.0)), "ft.dir"),
    (dict(ft=dict(fault_drop_prob=1.5)), "probability"),
    # PR-9 knob: model.kernels dispatch strings (repro.kernels.interface)
    (dict(model=dict(kernels="cuda")), "model.kernels"),
    (dict(model=dict(kernels="xla_associative")), "attention"),
    (dict(model=dict(kernels="attention=xla_associative")),
     "ssm_scan={pallas|xla|xla_associative}"),
    (dict(model=dict(kernels="flash=pallas")), "unknown op"),
    (dict(model=dict(kernels="")), "non-empty"),
])
def test_invalid_combos_raise_actionable_spec_errors(mutate, needle):
    base = RunSpec().to_dict()
    for section, fields in mutate.items():
        base[section].update(fields)
    with pytest.raises(SpecError) as e:
        RunSpec.from_dict(base)
    assert needle.lower() in str(e.value).lower(), \
        f"error not actionable: {e.value}"


@pytest.mark.parametrize("section,field,value,replacement", [
    ("ps", "kind", "mono", "ps.kind='sharded' with ps.shards=1"),
    ("ps", "apply", "tree", "fused Pallas apply"),
    ("ps", "apply", "packed", "fused Pallas apply"),
    ("wire", "format", "tree", "packed (rows, 512) buffer"),
])
def test_removed_choices_name_their_replacement(section, field, value,
                                                replacement):
    """A spec from JSON that names the removed monolithic server, tree
    or packed apply, or tree wire is refused with what replaces it."""
    d = RunSpec(ps=ServerSpec(kind="sharded", shards=1)).to_dict()
    d[section][field] = value
    with pytest.raises(SpecError) as e:
        RunSpec.from_dict(json.loads(json.dumps(d)))
    assert f"{section}.{field}={value!r}" in str(e.value)
    assert replacement in str(e.value)


@pytest.mark.parametrize("config", ["chip_smoke", "danube-ps",
                                    "moonlight-ps"])
def test_frozen_harness_specs_still_validate(config):
    """The benchmark harness and ``chip_smoke.py`` still pass
    ``ps.apply`` and ``wire.format``: their values are the one apply and
    the one wire, and the specs build the threaded PS engine."""
    root = SCHEMA_PATH.parents[3]
    if config == "chip_smoke":
        server, wire = {"apply": "fused", "gating": "sharded"}, \
            {"format": "packed", "delta_pull": False}
    else:
        cfg = json.loads((root / "benchmarks" / "chip" / "configs"
                          / f"{config}.json").read_text())
        server, wire = cfg["server"], cfg["wire"]
    spec = RunSpec(ps=ServerSpec(kind="sharded", shards=4, workers=1,
                                 apply=server["apply"],
                                 gating=server["gating"]),
                   wire=WireSpec(format=wire["format"],
                                 delta_pull=wire["delta_pull"]))
    assert spec.engine == "ps-threads"
    assert spec.ps == ServerSpec(kind="sharded", shards=4, workers=1)
    assert spec.wire == WireSpec()


def test_from_dict_rejects_unknown_keys():
    d = RunSpec().to_dict()
    d["psx"] = {}
    with pytest.raises(SpecError, match="psx"):
        RunSpec.from_dict(d)
    d2 = RunSpec().to_dict()
    d2["sync"]["staleness_bound"] = 3
    with pytest.raises(SpecError, match="staleness_bound"):
        RunSpec.from_dict(d2)


def test_from_dict_missing_sections_use_defaults():
    spec = RunSpec.from_dict({"sync": {"mode": "ssp", "staleness": 4}})
    assert spec.sync.staleness == 4
    assert spec.ps == ServerSpec()


def test_json_roundtrip_bitwise():
    spec = RunSpec(sync=SyncSpec(mode="dssp", s_lower=2, s_upper=9),
                   ps=ServerSpec(kind="sharded", shards=4, workers=3,
                                 straggler=2.5),
                   wire=WireSpec(compression="topk",
                                 topk_fraction=0.125),
                   transport=TransportSpec(kind="tcp", port=7001))
    assert RunSpec.from_json(spec.to_json()) == spec


def test_schema_lock_matches_checked_in_file():
    """The CI API-surface lock, enforced as a test too: regenerate with
    ``python -m repro.api --dump-schema > src/repro/api/schema.json``
    whenever the spec surface changes (that diff IS the review)."""
    on_disk = json.loads(SCHEMA_PATH.read_text())
    assert dump_schema() == on_disk, (
        "RunSpec surface drifted from src/repro/api/schema.json — "
        "regenerate it (python -m repro.api --dump-schema) and review "
        "the diff")


def test_build_session_rejects_unknown_overrides():
    with pytest.raises(SpecError, match="override"):
        build_session(RunSpec(), frobnicate=1)


# ======================================================= session engines
def test_threaded_sharded_session_matches_manual_wiring():
    """The api-built sharded run applies exactly like the pre-refactor
    direct wiring (single worker => deterministic push sequence)."""
    from repro.core.policies import make_policy_factory
    from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
    from repro.ps.step import packed_step
    from repro.ps.worker import PSWorker, run_cluster

    params, step, batches = tiny_problem()
    spec = RunSpec(model=ModelSpec(arch="custom"),
                   optimizer=OptimizerSpec(lr=0.3),
                   sync=SyncSpec(mode="ssp", staleness=2),
                   ps=ServerSpec(kind="sharded", shards=2, workers=1))
    with build_session(spec, params=params, step_fn=step,
                       batches=lambda w: batches(w, 1)) as session:
        session.run(12)
        api_params = session.server.params

    manual = ShardedParameterServer(
        params, make_policy_factory("ssp", n_workers=1, staleness=2),
        lambda: ServerOptimizer(lr=0.3), 1, 2)
    workers = [PSWorker(0, manual, packed_step(manual.plan, step),
                        batches(0, 1), 12,
                        loss_from_aux=lambda a: float(a["loss"]))]
    run_cluster(manual, workers, timeout=120.0)
    for a, b in zip(jax.tree_util.tree_leaves(api_params),
                    jax.tree_util.tree_leaves(manual.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spmd_session_matches_direct_trainer():
    """build_session(spmd spec) == Trainer(...) bitwise (SSP: the delay
    is fixed, so the run is deterministic)."""
    from repro.configs import get_smoke_config
    from repro.data.synthetic import DataConfig
    from repro.launch.train import Trainer

    spec = RunSpec(model=ModelSpec(arch="xlstm-125m"),
                   data=DataSpec(seq_len=16, global_batch=4),
                   optimizer=OptimizerSpec(lr=5e-3),
                   sync=SyncSpec(mode="ssp", s_lower=1, s_upper=3))
    with build_session(spec) as session:
        m = session.run(5)

    cfg = get_smoke_config("xlstm-125m")
    trainer = Trainer(cfg, DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=16, global_batch=4),
                      sync="ssp", s_lower=1, s_upper=3, lr=5e-3)
    log = trainer.train(5)
    assert m["final_loss"] == log.losses[-1]
    assert m["first_loss"] == log.losses[0]


def test_external_workers_session_refuses_run():
    params, _, _ = tiny_problem()
    spec = RunSpec(model=ModelSpec(arch="custom"),
                   sync=SyncSpec(mode="asp"),
                   ps=ServerSpec(kind="sharded", shards=2, workers=1))
    session = build_session(spec, params=params, external_workers=True)
    session.start()
    with pytest.raises(SpecError, match="external"):
        session.run(1)
    assert session.server is not None
    session.close()
    assert session.server.stopped


def test_custom_arch_without_overrides_is_actionable():
    spec = RunSpec(model=ModelSpec(arch="custom"),
                   ps=ServerSpec(kind="sharded", shards=1, workers=1),
                   sync=SyncSpec(mode="asp"))
    session = build_session(spec)
    with pytest.raises(SpecError, match="overrides"):
        session.start()


# ================================================== deprecation shims
def test_legacy_construction_warns_once_and_behaves_identically():
    from repro.core.policies import make_policy_factory
    from repro.ps.sharded import ServerOptimizer, ShardedParameterServer

    params = {"w": jnp.zeros((6, 3))}

    def construct():
        return ShardedParameterServer(
            params, make_policy_factory("asp", n_workers=1),
            lambda: ServerOptimizer(lr=0.1), 1, 1)

    _compat.reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        legacy = construct()
    dep = [w for w in caught
           if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "repro.api" in str(dep[0].message)

    # a second construction does NOT warn again (single warning)
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        construct()
    assert not [w for w in caught2
                if issubclass(w.category, DeprecationWarning)]

    # the api-built server never warns and applies identically
    spec = RunSpec(model=ModelSpec(arch="custom"),
                   optimizer=OptimizerSpec(lr=0.1),
                   sync=SyncSpec(mode="asp"),
                   ps=ServerSpec(kind="sharded", shards=1, workers=1))
    _compat.reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as caught3:
        warnings.simplefilter("always")
        session = build_session(spec, params=params,
                                external_workers=True).start()
    assert not [w for w in caught3
                if issubclass(w.category, DeprecationWarning)]
    grads = legacy.plan.pack({"w": jnp.ones((6, 3))})
    legacy.push_packed(0, grads)
    session.server.push_packed(0, grads)
    np.testing.assert_array_equal(np.asarray(legacy.params["w"]),
                                  np.asarray(session.server.params["w"]))
    session.close()
    legacy.stop()


def test_train_ps_shim_warns_and_trains():
    from repro.configs import get_smoke_config
    from repro.data.synthetic import DataConfig
    from repro.launch.train import train_ps

    cfg = get_smoke_config("xlstm-125m")
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                          global_batch=4)
    with pytest.warns(DeprecationWarning, match="build_session"):
        server = train_ps(cfg, data_cfg, sync="bsp", n_steps=2, lr=1e-2,
                          n_shards=2, n_workers=2, arch="xlstm-125m")
    assert server.version > 0
    assert server.stopped


def test_worker_task_carries_delta_pull():
    from repro.launch.proc_pool import WorkerTask

    spec = RunSpec(model=ModelSpec(arch="xlstm-125m"),
                   ps=ServerSpec(kind="sharded", shards=2, workers=2,
                                 coalesce=2),
                   wire=WireSpec(delta_pull=True),
                   transport=TransportSpec(kind="tcp"))
    task = WorkerTask.from_spec(spec, 3)
    assert task.delta_pull is True
    assert task.to_dict()["delta_pull"] is True  # crosses the spawn


def test_cli_spec_rejects_every_wiring_flag():
    """--spec is the single source of truth: ANY wiring flag alongside
    it must be rejected, not silently ignored."""
    import subprocess
    import sys

    spec_path = "/tmp/test_api_cli_spec.json"
    with open(spec_path, "w") as f:
        f.write(RunSpec().to_json())
    for extra in (["--ps-gating", "global"], ["--lr", "0.1"],
                  ["--compress", "int8"], ["--ps-workers", "8"]):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.train",
             "--spec", spec_path, *extra],
            capture_output=True, text=True,
            env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin:/usr/local/bin"},
            cwd=str(SCHEMA_PATH.parents[3]))
        assert proc.returncode == 2, (extra, proc.stderr)
        assert "single source of truth" in proc.stderr


# ====================================== spec-driven process transport
def test_tcp_spec_run_matches_prerefactor_wiring_bitwise():
    """One worker (deterministic push sequence) through --spec-style
    build_session vs the literal pre-refactor manual wiring: identical
    final packed parameters."""
    from repro.configs import get_smoke_config
    from repro.core.policies import make_policy_factory
    from repro.launch.proc_pool import (ProcessWorkerPool, WorkerTask,
                                        raise_on_failure)
    from repro.models import registry
    from repro.ps.sharded import ServerOptimizer, ShardedParameterServer
    from repro.transport import PSServerEndpoint, make_transport

    steps, seq, batch = 3, 16, 4
    spec = RunSpec(model=ModelSpec(arch="xlstm-125m"),
                   data=DataSpec(seq_len=seq, global_batch=batch),
                   optimizer=OptimizerSpec(lr=3e-3),
                   sync=SyncSpec(mode="dssp", staleness=1, s_lower=1,
                                 s_upper=3),
                   ps=ServerSpec(kind="sharded", shards=2, workers=1),
                   transport=TransportSpec(kind="tcp"))
    with build_session(spec) as session:
        m = session.run(steps)
        spec_wire = np.asarray(session.server.pull_packed())
    assert m["iterations_done"] == steps

    # ---- the pre-refactor wiring, by hand ----
    cfg = get_smoke_config("xlstm-125m")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        server = ShardedParameterServer(
            params,
            make_policy_factory("dssp", n_workers=1, staleness=1,
                                s_lower=1, s_upper=3),
            lambda: ServerOptimizer(lr=3e-3), 1, 2)
    endpoint = PSServerEndpoint(server)
    tp = make_transport("tcp", n_workers=1)
    tp.serve(endpoint)
    task = WorkerTask(arch="xlstm-125m", n_shards=2, n_iterations=steps,
                      smoke=True, seq_len=seq, global_batch=batch)
    pool = ProcessWorkerPool(tp.address(), task, 1)
    pool.start()
    try:
        results = pool.join(timeout=600.0, endpoint=endpoint)
    finally:
        server.stop()
        tp.shutdown()
        pool.terminate()
    raise_on_failure(results)
    manual_wire = np.asarray(server.pull_packed())
    np.testing.assert_array_equal(spec_wire, manual_wire)
