"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: JAX decides once per process, at its
first compile, whether and where the cache is used."""
import os
import pathlib
import sys

import pytest

from repro.launch import compile_cache
from support import run_child

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import os, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = sys.argv[1]
path = compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def test_default_cache_dir_is_fixed_under_the_checkout():
    assert pathlib.Path(compile_cache.DEFAULT_DIR) == ROOT / ".cache" / "jax"


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compiled_programs_land_in_the_cache_dir(tmp_path, env_set):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    env.pop(compile_cache.ENV, None)
    want = tmp_path / "cache"
    argv = [sys.executable, "-c", SCRIPT]
    if env_set:
        env[compile_cache.ENV] = str(want)
        # the fallback must not be consulted when the variable is set
        argv.append(str(tmp_path / "not_used"))
    else:
        argv.append(str(want))
    proc = run_child(argv, timeout=60, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout.split()
    assert out[-2:] == [str(want), str(want)]
    assert any(name.endswith("-cache") for name in os.listdir(want))
    assert not (tmp_path / "not_used").exists()
