"""The trainer's entry point: ``main(argv)`` in-process, the depth
cut it prints, and the one place the compilation cache lives."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch import cache, train


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings: ``use_compile_cache`` changes
    process-wide config."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _cache_files(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cache_dir_from_env_is_the_only_one_written(
        monkeypatch, tmp_path, cache_config):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = _cache_files(cache.CHECKOUT_CACHE)
    assert cache.use_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3.0 - 7.0)(jnp.arange(11.0)).block_until_ready()
    assert _cache_files(tmp_path)
    assert _cache_files(cache.CHECKOUT_CACHE) == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.use_compile_cache()
    assert path == str(cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.basename(path) == ".jax_cache"
    assert os.path.isdir(os.path.join(os.path.dirname(path), "src", "repro"))


def test_main_argv_in_process(monkeypatch, tmp_path, capsys, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    engine = train.main(["--steps", "4", "--replicas", "2", "--batch", "2",
                         "--seq", "32", "--layers", "1",
                         "--warmup-sync", "1"])
    out = capsys.readouterr().out
    assert f"compile cache: {tmp_path}" in out
    assert "depth 1 of 16 published layers" in out
    assert "reduced widths: d_model=128" in out
    assert len(engine.history.losses) == 4
    assert engine.history.sync_steps[0] == 0
    assert len(engine.W["blocks"]) == 1


@pytest.mark.parametrize("argv", [["--layers", "3"], ["--layers", "-1"]])
def test_main_rejects_depth_beyond_the_model(argv, cache_config):
    with pytest.raises(SystemExit):
        train.main(argv)        # the reduced model has 2 layers
