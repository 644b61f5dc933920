"""Where the entry points keep JAX's persistent compilation cache
(launch/cache.py): `JAX_COMPILATION_CACHE_DIR` when it is set, else the
fixed `<repo>/.jax_cache`. Each case runs in a fresh interpreter, since
JAX reads the variable once, at import."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.launch.cache import REPO_CACHE_DIR, use_compile_cache
    got = use_compile_cache()
    out = {"returned": got, "config": jax.config.jax_compilation_cache_dir,
           "repo_dir": str(REPO_CACHE_DIR)}
    if len(sys.argv) > 1:        # compile once, so that an entry is written
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    print(json.dumps(out))
""")


def _run(env_dir, compile_once=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _SCRIPT] + (["compile"] if compile_once
                                               else [])
    proc = subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placed", [True, False], ids=["env", "repo"])
def test_compile_cache_location(tmp_path, placed):
    got = _run(tmp_path / "cache" if placed else None)
    want = str(tmp_path / "cache") if placed else str(REPO / ".jax_cache")
    assert got["returned"] == want
    assert got["config"] == want
    assert got["repo_dir"] == str(REPO / ".jax_cache")


def test_compile_cache_written_where_placed(tmp_path):
    """A compile under a placed cache directory lands in that directory."""
    cache = tmp_path / "cache"
    _run(cache, compile_once=True)
    assert cache.is_dir() and any(cache.iterdir())
