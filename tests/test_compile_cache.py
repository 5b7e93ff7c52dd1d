"""enable_compile_cache: JAX_COMPILATION_CACHE_DIR when set, else the fixed
``<checkout>/.jax_cache``; compiled programs land in that directory only.

Each case runs in a child process on the CPU, so the cache setting never
leaks into the other tests of this worker."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CHILD = """
import sys
from pathlib import Path
from repro.runtime import compile_cache
compile_cache.CHECKOUT = Path(sys.argv[1])
path = compile_cache.enable_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()
print(path)
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_cache_lands_in_one_place(tmp_path, from_env):
    checkout, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHILD, str(checkout)],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    want, other = ((env_dir, checkout / ".jax_cache") if from_env
                   else (checkout / ".jax_cache", env_dir))
    assert r.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir()), "no compiled program was cached"
    assert not other.exists()
