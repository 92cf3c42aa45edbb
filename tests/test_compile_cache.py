"""``launch.compile_cache``: the entry points' persistent compilation
cache. Each case runs in a child process, since the helper sets
process-wide JAX config."""
from __future__ import annotations

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD = r"""
import json, logging, pathlib, sys
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.CHECKOUT = pathlib.Path(sys.argv[1])
path = compile_cache.enable_compile_cache()
import jax, jax.numpy as jnp

hits = []
class Hits(logging.Handler):
    def emit(self, record):
        if "cache hit" in record.getMessage().lower():
            hits.append(record.getMessage())
log = logging.getLogger("jax._src.compiler")
log.setLevel(logging.DEBUG)
log.addHandler(Hits())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir,
                  "hits": len(hits)}))
"""


def _run(env_dir=None, checkout=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _CHILD] + ([str(checkout)] if checkout else [])
    out = subprocess.run(argv, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _entries(d):
    return sum(len(files) for _, _, files in os.walk(d))


def test_env_dir_is_used_as_is(tmp_path):
    d = tmp_path / "jaxcache"
    rec = _run(env_dir=d)
    assert rec["path"] == str(d) and rec["config"] == str(d)
    assert _entries(d) > 0


def test_default_dir_is_inside_the_checkout(tmp_path):
    rec = _run(checkout=tmp_path)
    want = str(tmp_path / ".jax_cache")
    assert rec["path"] == want and rec["config"] == want
    assert _entries(want) > 0


def test_second_run_hits_the_cache(tmp_path):
    first = _run(env_dir=tmp_path)
    n = _entries(tmp_path)
    second = _run(env_dir=tmp_path)
    assert first["hits"] == 0
    assert second["hits"] > 0
    assert _entries(tmp_path) == n
