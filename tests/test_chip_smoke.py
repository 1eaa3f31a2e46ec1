"""``chip_smoke.py`` off the chip: it refuses a CPU backend, and its
phases run at smoke size on the CPU (the four-chip phase on four
virtual CPU devices, in a subprocess since this process has one)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(code: str, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_a_cpu_backend():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip(), out.stdout   # no result line


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=dict(env, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip(), out.stdout


def test_chip_smoke_phases_run_at_smoke_size():
    session = chip_smoke.ps_phase(platform="cpu", smoke=True, seq=32,
                                  batch=2)
    try:
        chip_smoke.parity_phase(session, compiled_kernels=False)
    finally:
        session.close()
    chip_smoke.spmd_phase(smoke=True, seq=32, batch=2, steps=3)


def test_four_chip_phase_on_four_virtual_devices():
    code = textwrap.dedent("""
        import json, jax
        import chip_smoke
        devs = jax.devices()
        mesh = chip_smoke.four_chip_mesh(devs[:4])
        chip_smoke.four_chip_phase(devs[:4], smoke=True, seq=32, batch=4)
        print(json.dumps({"shape": dict(mesh.shape),
                          "axes": list(mesh.axis_names),
                          "devices": [d.id for d in mesh.devices.flat]}))
    """)
    out = _run(code, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"shape": {"data": 2, "model": 2},
                    "axes": ["data", "model"], "devices": [0, 1, 2, 3]}
    assert "parameter bytes per device" in out.stdout
