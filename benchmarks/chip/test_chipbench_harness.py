"""The harness finds every piece by name, refuses a machine without the
chips a cell asks for, and runs nothing without the program."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chip import run
from chip.conftest import ROOT


def _files(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "benchmarks" / "chip").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _copy_benchmark(dst):
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip", dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_every_cell_finds_its_files():
    bench = run.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        _, work, cfg, traffic, limits = run.load_cell(ROOT, w["name"])
        assert cfg["program"]["engine"] in ("ps-threads", "spmd")
        assert traffic["seq_len"] > 0 and limits
    for m in bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))


def test_new_cell_and_metric_need_no_edit_of_existing_files(tmp_path):
    _copy_benchmark(tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    before = _files(tmp_path)
    # a later change adds files and BENCHMARK.json entries, nothing else
    (here / "traffic" / "short.json").write_text(json.dumps(
        {"workers": 1, "speed_factors": [1.0], "batch": 1,
         "seq_len": 512}))
    cfg = json.loads((here / "configs" / "danube-ps.json").read_text())
    (here / "configs" / "danube-ps-s8.json").write_text(json.dumps(
        dict(cfg, server=dict(cfg["server"], shards=8))))
    (here / "limits" / "danube-ps-s8.short.json").write_text(json.dumps(
        {"loss_gap": 1.0, "grad1_gap": 1.0, "change_gap": 1.0}))
    (here / "metrics" / "pull_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="danube-ps-s8",
                                 file="benchmarks/chip/configs/"
                                      "danube-ps-s8.json"))
    bench["workloads"].append({"name": "danube-ps-s8.short",
                               "config": "danube-ps-s8",
                               "traffic": "short", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    _, work, cfg8, traffic, limits = run.load_cell(tmp_path,
                                                   "danube-ps-s8.short")
    assert cfg8["server"]["shards"] == 8 and traffic["seq_len"] == 512
    assert limits["loss_gap"] == 1.0
    assert run.load_reader("pull_share", here)({}) == 42.0
    after = _files(tmp_path)
    assert all(after[p] == h for p, h in before.items())


#: A model family under key names of its own, written by the test below:
#: it delegates to the dense family beside it and counts every call.
RENAMED = '''"""The dense family under its own key names."""

import collections
from pathlib import Path

from chip import cells

dense = cells.load_family("dense", Path(__file__).resolve().parents[1])
#: this family's key -> the dense family's
NAMES = {"d_model": "hidden_size", "d_ff": "intermediate_size",
         "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
         "n_layers": "num_hidden_layers", "vocab_size": "vocab_size",
         "window": "sliding_window", "theta": "rope_theta",
         "eps": "rms_norm_eps"}
CALLS = collections.Counter()


def _dense(m):
    return {NAMES[k]: v for k, v in m.items()}


def _mine(d):
    return {k: d[v] for k, v in NAMES.items() if v in d}


SMOKE = dict(dense.SMOKE, config=dict(_mine(dense.SMOKE["config"]),
                                      torch_dtype="float32"))


def sizes(cfg):
    CALLS["sizes"] += 1
    return {k: cfg[k] for k in NAMES}


def program_sizes(c):
    CALLS["program_sizes"] += 1
    return _mine(dense.program_sizes(c))


def init_params(m, key, dtype, *, eager=False):
    CALLS["init_params"] += 1
    return dense.init_params(_dense(m), key, dtype, eager=eager)


def loss_and_grad(m, params, tokens, labels, prec="f32", with_grad=True,
                  half=False):
    CALLS["loss_and_grad"] += 1
    return dense.loss_and_grad(_dense(m), params, tokens, labels, prec,
                               with_grad, half)


def param_count(m):
    CALLS["param_count"] += 1
    return dense.param_count(_dense(m))


def train_flops_per_token(m, seq):
    CALLS["train_flops_per_token"] += 1
    return dense.train_flops_per_token(_dense(m), seq)
'''


def test_new_family_needs_no_edit_of_existing_files(tmp_path):
    """A configuration of a model family the harness has never seen
    comes in as new files and BENCHMARK.json entries, and its cell runs
    whole and correct, every model-specific step through its family."""
    from chip import cells, peaks
    from chip.conftest import SMOKE_SEQ, run_smoke
    _copy_benchmark(tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    before = _files(tmp_path)
    (here / "families" / "dense_renamed.py").write_text(RENAMED)
    base = json.loads((here / "configs" / "danube-ps.json").read_text())
    fam = cells.load_family("dense_renamed", here)
    dense = cells.load_family("dense", here)
    cfg = {k: v for k, v in base.items() if k not in dense.KEYS}
    cfg.update(fam._mine(base), family="dense_renamed")
    (here / "configs" / "renamed-ps.json").write_text(json.dumps(cfg))
    (here / "traffic" / "one.json").write_text(json.dumps(
        {"workers": 1, "speed_factors": [1.0], "batch": 1,
         "seq_len": 2048}))
    (here / "limits" / "renamed-ps.one.json").write_text(json.dumps(
        {"loss_gap": 1e-4, "grad1_gap": 1e-4, "change_gap": 1e-4}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="renamed-ps",
                                 file="benchmarks/chip/configs/"
                                      "renamed-ps.json"))
    bench["workloads"].append({"name": "renamed-ps.one",
                               "config": "renamed-ps", "traffic": "one",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run_smoke("renamed-ps.one", root=tmp_path)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0 and out["failed"] == 0
    # the weights (the session's and the replay's) and every reference
    # step went through the new family, and the registry check with it
    assert fam.CALLS["init_params"] == 2
    assert fam.CALLS["loss_and_grad"] == cells.CHECK_STEPS
    assert fam.CALLS["program_sizes"] == 1

    # the readers divide by the new family's counts, and the attention
    # readers, which look for keys it does not have, give nothing
    m = fam.sizes(fam.SMOKE["config"])
    want = dense.sizes(dense.SMOKE["config"])
    apply_op = ('%_fused_update.1 = bf16[8] custom-call(a), '
                'custom_call_target="tpu_custom_call"')
    ctx = {"family": fam, "model": m, "engine": "ps-threads",
           "config": cfg, "traffic": {"batch": 1, "seq_len": SMOKE_SEQ},
           "counters": {"tokens": 2048, "seconds": 1.0}, "window_s": 1.0,
           "n_chips": 1, "peak": peaks.peak("TPU v5 lite"),
           "ops": {"/device:TPU:0": [(apply_op, 0.0, 1e6)]},
           "phase_seconds": {"attn_bwd": 0.5}}
    mfu = run.load_reader("mfu", here)(ctx)
    assert mfu == pytest.approx(
        100 * 2048 * dense.train_flops_per_token(want, SMOKE_SEQ) / 197e12)
    assert fam.CALLS["train_flops_per_token"] == 1
    applied = run.load_reader("apply_roofline", here)(ctx)
    assert applied["calls"] == 1 and fam.CALLS["param_count"] == 1
    for name in ("attn_fwd_roofline", "attn_bwd_roofline"):
        assert run.load_reader(name, here)(ctx) is None

    after = _files(tmp_path)
    assert all(after[p] == h for p, h in before.items())


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "danube-ps.solo", "--seed", "3", "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def _printed_a_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_refuses_a_cpu_backend():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert "no CPU fallback" in out.stderr
    assert not _printed_a_result(out.stdout)


def test_runs_nothing_without_the_program(tmp_path):
    _copy_benchmark(tmp_path)
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert not _printed_a_result(out.stdout)
