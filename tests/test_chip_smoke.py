"""CPU guard for ``chip_smoke.py``: its phases at a tiny size (Pallas in
interpret mode), its refusal to report on a CPU, and its four-chip layout on
four virtual CPU devices."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_newton_phase_matches_reference(smoke, capsys):
    check = smoke.Checks()
    out = smoke.phase_newton(check, n=20_000, seed=3)
    assert check.failed == []
    assert out["beta_rel_err"] <= smoke.BETA_RTOL
    assert "check A.blocks_on_device: ok" in capsys.readouterr().out


def test_dgemm_phase_matches_reference(smoke, capsys):
    check = smoke.Checks()
    out = smoke.phase_dgemm(check, n=256, rows=16, seed=3)
    assert check.failed == []
    assert out["dgemm_rel_err"] <= smoke.DGEMM_RTOL
    # off the TPU the kernel runs interpreted, so no Mosaic call is lowered
    assert "tpu_custom_call in the 64x64 block matmul's HLO: False" in \
        capsys.readouterr().out


def test_failed_check_is_recorded(smoke, capsys):
    check = smoke.Checks()
    check("x", False, "detail")
    check("y", True, "detail")
    assert check.failed == ["x"]
    assert "check x: FAILED (detail)" in capsys.readouterr().out


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform': 'cpu'" in out


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


def test_four_chip_layout_on_virtual_devices():
    code = f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        failed = chip_smoke.run_phases(
            4, seed=1, newton_sizes={{"n": 32_000}},
            dgemm_sizes={{"n": 512, "rows": 16}})
        print("FAILED", failed)
    """
    out = _run(code, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert "FAILED []" in out, out[-3000:]
    for phase in ("A", "B"):
        # node i's blocks sit on jax.devices()[i], for all four nodes
        assert f"check {phase}.blocks_on_device: ok" in out
        assert (f"check {phase}.nodes_on_devices: ok "
                "(nodes holding blocks [0, 1, 2, 3])") in out
        assert f"check {phase}.device_moves: ok" in out
