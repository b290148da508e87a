"""How the chip scripts and entry points come up: where the persistent
compile cache goes, and that a short device count raises instead of falling
back to another platform."""

import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import __graft_entry__  # noqa: E402
from petastorm_tpu.jax.compile_cache import use_persistent_compile_cache  # noqa: E402


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_compilation_cache_dir
    yield saved
    jax.config.update('jax_compilation_cache_dir', saved)
    compilation_cache.reset_cache()


def test_compile_cache_env_set_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    assert use_persistent_compile_cache(REPO_ROOT) == str(tmp_path)
    # nothing set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_compile_cache_env_unset_uses_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    expected = os.path.join(REPO_ROOT, '.jax_cache')
    assert use_persistent_compile_cache(REPO_ROOT) == expected
    assert jax.config.jax_compilation_cache_dir == expected
    # fixed: no pid, temp name or time in it, so a second run finds it again
    assert use_persistent_compile_cache(REPO_ROOT) == expected


@pytest.mark.parametrize('platforms', ['cpu', None])
def test_ensure_devices_raises_instead_of_respawning(monkeypatch, platforms):
    have = len(jax.devices())  # the suite's CPU backend, up before the env changes
    if platforms is None:
        monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    else:
        monkeypatch.setenv('JAX_PLATFORMS', platforms)

    def no_child(*args, **kwargs):
        raise AssertionError('bring-up must not start a child process')

    monkeypatch.setattr(subprocess, 'run', no_child)
    monkeypatch.setattr(subprocess, 'Popen', no_child)
    __graft_entry__._ensure_devices(have)  # enough: no error
    with pytest.raises(RuntimeError, match='need {} devices'.format(have + 1)):
        __graft_entry__._ensure_devices(have + 1)
