"""The persistent-compilation-cache helper of the entry points.

`enable_compile_cache()` is only ever called by the command-line entry
points and `chip_smoke.py`, so the tests that call it run it in a child
process that compiles nothing."""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json
import jax
from jax._src import xla_bridge
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "backend_initialized": xla_bridge.backends_are_initialized()}))
"""


def test_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.compile_cache_dir() == os.path.join(
        CHECKOUT, ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_sets_only_the_resolved_dir(from_env, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(CHECKOUT, "src")
    env.pop(compile_cache.ENV_VAR, None)
    want = os.path.join(CHECKOUT, ".jax_cache")
    if from_env:
        env[compile_cache.ENV_VAR] = want = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"path": want, "config": want,
                   "backend_initialized": False}
