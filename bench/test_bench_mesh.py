"""The four-chip traffic (``bench/traffic/score_x4.json``: the trial
axis sharded over every chip) on four forced host devices: the sharded
stream agrees with the reference's per-device merge, and leaving the
cross-device exchange out makes the run incorrect."""
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

SCRIPT = r"""
import importlib.util, json, os, sys
import jax
from harness import cells, faults
spec = importlib.util.spec_from_file_location("bench_run", "bench/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
cell = cells.load_cell("ffp_n11_lan.score", ".")
with open("bench/traffic/score_x4.json") as f:
    cell.traffic = dict(json.load(f), trials=20_003, chunk=2_048)
cell.chips = 4
assert len(jax.devices()) == 4
out = {"sound": run.run_cell(cell, 2**33 + 1, 0.2, False, jax.devices())}
with faults.no_exchange():
    out["no_exchange"] = run.run_cell(cell, 2**33 + 1, 0.2, False,
                                      jax.devices())
print(json.dumps({k: [v["correct"], v["checks"]] for k, v in out.items()}))
"""


def test_four_device_mesh_and_the_missing_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           BENCH]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["sound"][0] is True, res["sound"][1]
    assert res["no_exchange"][0] is False
    assert res["no_exchange"][1]["count_gap"]["value"] > 0
