"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a fresh interpreter, since the helper changes process-wide
JAX configuration and the environment that spawned ranks inherit.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import os, sys, jax
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = sys.argv[1]
path = compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
print(os.environ[compile_cache.ENV])
"""


def _probe(env_dir, default_dir):
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, str(default_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_goes_to_env_dir_or_fixed_default(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the cache lands there and
    nowhere else; unset, it lands in the default directory, which is then
    exported for spawned ranks."""
    env_dir, default_dir = tmp_path / "from_env", tmp_path / "default"
    chosen, config_dir, exported = _probe(env_dir if env_set else None,
                                          default_dir)
    want, other = ((env_dir, default_dir) if env_set
                   else (default_dir, env_dir))
    assert chosen == config_dir == exported == str(want)
    assert any(want.iterdir()), "nothing was cached"
    assert not other.exists()


def test_default_dir_is_fixed_and_ignored():
    """The default is one fixed path inside the checkout, and git ignores
    it."""
    assert compile_cache.DEFAULT_DIR == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
