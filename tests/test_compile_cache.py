"""The entry points' persistent compilation cache lands where it should:
in ``JAX_COMPILATION_CACHE_DIR`` when that is set, and nowhere else;
otherwise at the fixed ``<checkout>/.jax_cache``."""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT_CACHE = REPO / ".jax_cache"

_COMPILE = """
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
"""


def _run(env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    # cache every compile, however short, so one tiny jit leaves an entry
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    r = subprocess.run([sys.executable, "-c", _COMPILE], capture_output=True,
                       text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def _entries(path: pathlib.Path) -> set:
    return set(os.listdir(path)) if path.is_dir() else set()


def test_cache_goes_to_env_dir_and_nowhere_else(tmp_path):
    target = tmp_path / "cache"
    before = _entries(CHECKOUT_CACHE)
    used = _run({"JAX_COMPILATION_CACHE_DIR": str(target)})
    assert used == str(target)
    assert any(name.endswith("-cache") for name in _entries(target))
    assert _entries(CHECKOUT_CACHE) == before


def test_cache_defaults_to_checkout_dir():
    used = _run({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert used == str(CHECKOUT_CACHE)
    assert any(name.endswith("-cache") for name in _entries(CHECKOUT_CACHE))
