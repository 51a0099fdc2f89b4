"""``bench/run.py`` as its command line starts it: without a TPU it exits
non-zero and prints no result, and in a directory that holds only
``BENCHMARK.json`` and the benchmark's files it does the same."""
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "ffp_n11_lan.score", "--seed", str(2 ** 31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_refuses_an_unknown_workload():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "nope", "--seed", "1", "--seconds", "1"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "unknown workload 'nope'" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
