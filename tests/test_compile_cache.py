"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to the checkout's .jax_cache — and nowhere else."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

_SRC = Path(__file__).resolve().parent.parent / "src"

# one cacheable compile; the checkout is redirected so the test can see
# which directory the default rule picks without touching the real one
_CHILD = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT = Path(sys.argv[1])
print(compile_cache.setup_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(tmp_path, env_dir):
    env = dict(os.environ, PYTHONPATH=str(_SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD, str(tmp_path / "co")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def _entries(path):
    return list(path.iterdir()) if path.exists() else []


def test_env_dir_is_the_only_cache(tmp_path):
    where = tmp_path / "env_cache"
    assert _run(tmp_path, where) == str(where)
    assert _entries(where)
    assert not _entries(tmp_path / "co" / ".jax_cache")


def test_default_is_the_checkout_cache(tmp_path):
    assert _run(tmp_path, None) == str(tmp_path / "co" / ".jax_cache")
    assert _entries(tmp_path / "co" / ".jax_cache")


def test_env_dir_is_left_to_jax(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == "/nonexistent/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_is_the_repo_root():
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
