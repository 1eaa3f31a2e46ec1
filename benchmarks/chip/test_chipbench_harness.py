"""The harness finds every piece by name, refuses a machine without the
chips a cell asks for, and runs nothing without the program."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

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
