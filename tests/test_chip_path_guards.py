"""Guards of the chip path that can be checked without a chip: where the
persistent compile cache lives, and that no benchmark pass times a CPU
child beside chip numbers."""

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them, so a test
    never turns the cache on for the rest of the worker."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_env_sets_nothing(monkeypatch, tmp_path,
                                         config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert config_updates == []


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch, tmp_path,
                                                   config_updates):
    repo = compile_cache.DEFAULT_DIR.parent
    assert (repo / "src" / "repro" / "utils" / "compile_cache.py").exists()
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = tmp_path / ".jax_cache"
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", where)
    assert compile_cache.enable() == str(where)
    assert where.is_dir()
    assert config_updates == [("jax_compilation_cache_dir", str(where))]


def test_serve_bench_sharded_pass_refuses_on_tpu(monkeypatch, capsys):
    """The sharded pass times a forced-CPU child process; on a TPU host
    it must say so and start nothing."""
    import subprocess

    from benchmarks import serve_bench

    def no_child(*a, **k):
        raise AssertionError("started a child process on a TPU host")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "run", no_child)
    serve_bench._sharded_pass(quick=True)
    assert "refused on a TPU host" in capsys.readouterr().err
