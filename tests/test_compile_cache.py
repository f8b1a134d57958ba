"""utils.compile_cache: the cache directory is placed from outside, or at
one fixed in-checkout path — never a tempfile, pid or time path (the path
is part of what a cache hit depends on)."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = ("import json, jax; "
          "from hivemall_tpu.utils.compile_cache import enable_compile_cache; "
          "d = enable_compile_cache(); "
          "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))")


def _probe(**env):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT, **env)
    r = subprocess.run([sys.executable, "-c", _PROBE], env=e, cwd="/",
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_wins_and_nothing_is_set_in_code(tmp_path):
    d = str(tmp_path / "outside")
    # jax's own env handling is what put it in the config
    assert _probe(JAX_COMPILATION_CACHE_DIR=d) == [d, d]


def test_default_dir_is_fixed_in_checkout_across_processes():
    want = os.path.join(_ROOT, ".jax_cache")
    assert _probe() == [want, want]
    assert _probe() == [want, want]          # a second process: same path


def test_default_dir_is_git_ignored():
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
