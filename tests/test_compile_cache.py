"""Placement of JAX's persistent compilation cache (repro.compile_cache)."""

import jax

from repro import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()  # never per-run
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    root = __file__.rsplit("/tests/", 1)[0]
    assert first == root + "/.jax_cache"


def test_import_touches_no_backend_and_no_cache():
    """Importing the package neither claims a device (a later process could
    not get the chip) nor places the cache: both happen only at call time."""
    import os
    import subprocess
    import sys

    script = (
        "import jax, repro, repro.core, repro.core.engine, repro.kernels.ops,"
        " repro.launch.serve, benchmarks.run\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert jax.config.jax_compilation_cache_dir is None\n")
    root = __file__.rsplit("/tests/", 1)[0]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([root + "/src", root]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
