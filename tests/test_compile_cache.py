"""The persistent compilation cache is placed from outside
(imaginaire_tpu/utils/compile_cache.py): where JAX_COMPILATION_CACHE_DIR
is set JAX reads it and no directory is set in code; where it is not,
the fixed ``<checkout>/.jax_cache``. Each case runs in a child process,
as an entry point would, so this process's cache stays where conftest
put it."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os, sys
sys.path.insert(0, {root!r})
import jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from imaginaire_tpu.utils import compile_cache
got = compile_cache.configure()
print(json.dumps({{"returned": got, "active": compile_cache.active_dir(),
                  "updates": updates,
                  "env": os.environ.get(compile_cache.ENV_VAR)}}))
"""


def _child(cwd, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CHILD.format(root=ROOT)],
                       capture_output=True, text=True, cwd=cwd, env=env,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_variable_set_no_directory_is_set_in_code(tmp_path):
    where = str(tmp_path / "cache")
    got = _child(str(tmp_path), where)
    assert got["updates"] == []          # nothing set in code
    assert got["returned"] == got["active"] == where  # JAX read it itself
    assert got["env"] == where           # still there for children


@pytest.mark.parametrize("cwd", ["checkout", "elsewhere"])
def test_variable_unset_fixed_path_from_any_directory(tmp_path, cwd):
    got = _child(ROOT if cwd == "checkout" else str(tmp_path), None)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert got["returned"] == got["active"] == fixed
    assert got["env"] is None


def test_hits_and_misses_are_counted():
    import jax

    from imaginaire_tpu.utils import compile_cache

    counts = compile_cache.count_events()
    assert counts == {"hits": 0, "misses": 0}
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/some/other/event")
    assert counts == {"hits": 1, "misses": 2}
