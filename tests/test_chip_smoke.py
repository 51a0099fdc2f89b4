"""``chip_smoke.py`` at tiny sizes on the CPU (no device check).

The chip run is the script's job; here its phases run small so a broken
phase is caught before any chip time is spent: the fused-kernel-vs-jnp
comparison (Pallas interpreted), the planner's cold/warm socket queries,
the means check, and the four-device comparison on four forced host
devices in a child process.
"""
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "needs a TPU" in str(e.value) and "'cpu'" in str(e.value)


def test_kernel_phase_matches_jnp_path_tiny():
    res = chip_smoke.phase_kernel(trials=6_000, chunk=2_048)
    assert res["n_systems"] == 13
    assert res["native"] is False              # interpreted on the CPU
    assert res["hist_entries_differing"] == 0
    assert res["quantile_bucket_steps_max"] == 0


def test_planner_phase_cold_then_warm_tiny():
    res = chip_smoke.phase_planner(trials=20_000)
    assert res["cold"]["engine_compiles"] >= 1
    assert res["warm"]["engine_compiles"] == 0


def test_means_check_tiny():
    assert chip_smoke.means_check(chunks=3, chunk=1_024) <= 1e-5


def test_four_device_comparison_on_forced_host_devices():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.four_chip(trials=100_003, "
         "chunk=4_096)"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "bit-identical" in proc.stdout
    assert "[25001, 25001, 25001, 25000]" in proc.stdout
