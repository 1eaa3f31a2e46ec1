"""Process-level JAX setup: the compile-cache location and the platform
pinned into spawned children (``repro.launch.runtime``)."""

from pathlib import Path

import jax
import pytest

from repro.launch import runtime

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process's cache setting (nothing compiles meanwhile)."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_the_environment_dir(monkeypatch, tmp_path,
                                                cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_one_ignored_checkout_path(monkeypatch,
                                                             cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache() == path   # fixed across calls
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_spawned_workers_pin_their_platform_instead_of_inheriting(
        monkeypatch):
    """A worker process that inherited the parent's JAX_PLATFORMS would
    fail to start on this bogus value; a pinned one computes on
    CHILD_PLATFORM and reports it."""
    from repro.api import (DataSpec, ModelSpec, RunSpec, ServerSpec,
                           TransportSpec, WireSpec, build_session)
    spec = RunSpec(model=ModelSpec(arch="h2o-danube-1.8b"),
                   data=DataSpec(seq_len=16, global_batch=2),
                   ps=ServerSpec(kind="sharded", shards=1, workers=1,
                                 apply="fused"),
                   wire=WireSpec(format="packed"),
                   transport=TransportSpec(kind="tcp"))
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    with build_session(spec) as session:
        metrics = session.run(2)
    assert metrics["worker_platforms"] == [runtime.CHILD_PLATFORM]
    assert metrics["iterations_done"] == 2
