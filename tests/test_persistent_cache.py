"""JAX's persistent compilation cache as ``repro.launch.persistent_cache``
turns it on, in a fresh process so the setting reaches no other test."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"), **env)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_persistent_cache_uses_the_given_directory(tmp_path):
    out = _run("""
        import os
        import jax, jax.numpy as jnp
        from repro.launch.persistent_cache import (DEFAULT_DIR,
                                                   enable_persistent_cache)
        print("DIR", enable_persistent_cache())
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
        print("ENTRIES", len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
        print("DEFAULT", DEFAULT_DIR)
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert f"DIR {tmp_path}\n" in out
    # a sub-second compile is kept (the threshold is 0 s)
    assert "ENTRIES 0" not in out
    # without the variable the cache goes to one fixed path in the checkout
    assert f"DEFAULT {os.path.join(REPO, '.jax_cache')}\n" in out
