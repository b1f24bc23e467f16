"""The entry points' persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
when set (and nothing else), ``<checkout>/.jax_cache`` otherwise."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_default_is_the_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_env_dir_receives_the_compiled_entries(tmp_path):
    target = tmp_path / "cache"
    default = os.path.join(REPO, ".jax_cache")
    before = _listing(default)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64))).block_until_ready()\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           compile_cache.ENV_VAR: str(target),
           "PYTHONPATH": os.path.join(REPO, "src")}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(target)
    assert any(name.startswith("jit__lambda") for name in os.listdir(target))
    assert _listing(default) == before
