"""bench/run.py refuses to run, and prints no result, without a TPU or
without the program."""
from bench_fixtures import REPO  # first: it puts the checkout on sys.path

import os
import shutil
import subprocess
import sys


ARGS = ["--workload", "lstm-asr.nghf-mpe.sausage", "--seed", "3000000017",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    return proc.returncode != 0 and "{" not in proc.stdout


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(REPO)
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    proc = _run(tmp_path)
    assert _no_result(proc), proc.stdout
    assert "not in this checkout" in proc.stderr
