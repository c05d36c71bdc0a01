"""repro.compile_cache: JAX_COMPILATION_CACHE_DIR wins when set, and
otherwise the cache is the checkout's fixed ``.jax_cache``.  Each case runs
in a child, so no test process ever turns the persistent cache on."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = r"""
import json, os
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
used = use_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # cache even this tiny program, to see where JAX writes it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
print(json.dumps({"used": used,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(env_dir, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if env_dir else os.path.join(ROOT, ".jax_cache")
    assert got == {"used": want, "config": want}
    if env_dir:
        assert os.listdir(tmp_path), "nothing was cached in the set directory"
