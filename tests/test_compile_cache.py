"""The one compile-cache rule (utils/compile_cache.py): placed from
outside by JAX_COMPILATION_CACHE_DIR and used as is, else
<checkout>/.jax_cache; importing the package alone sets none."""

import os
import subprocess
import sys

import jax
import pytest

from risingwave_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    orig_dir = jax.config.jax_compilation_cache_dir
    orig_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", orig_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", orig_min)


def test_env_var_directory_used_as_is(tmp_path, monkeypatch,
                                      restore_cache_config):
    want = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    got = cc.enable_persistent_cache()
    assert got == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert os.path.isdir(want) and os.listdir(want) == []


def test_unset_env_var_means_checkout_dot_jax_cache(monkeypatch,
                                                    restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cc.enable_persistent_cache()
    assert got == os.path.join(REPO, ".jax_cache") == cc.default_cache_dir()
    assert jax.config.jax_compilation_cache_dir == got
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_min_compile_secs_from_env_is_left_alone(tmp_path, monkeypatch,
                                                 restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "7")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 7.0)
    cc.enable_persistent_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 7.0


def test_importing_the_package_sets_no_cache():
    """A fresh interpreter: `import risingwave_tpu` must leave jax's
    cache directory unset (and must not create ~/.cache/rwtpu_xla)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    src = ("import jax, risingwave_tpu, os; "
           "assert jax.config.jax_compilation_cache_dir is None, "
           "jax.config.jax_compilation_cache_dir; "
           "assert 'JAX_COMPILATION_CACHE_DIR' not in os.environ; "
           "print('none-set')")
    p = subprocess.run([sys.executable, "-c", src], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "none-set" in p.stdout
