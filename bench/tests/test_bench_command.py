"""The benchmark's command refuses to run, and prints no result, without a
TPU, and in a checkout that holds the benchmark but not the program.
The command runs in a child process held to the CPU, so neither this file
nor the child loads the TPU library."""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "fpga-train-sgd", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def run_in(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_exits_nonzero_without_tpu():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("bad", [["--workload", "no-such-cell"], []])
def test_refuses_unknown_or_missing_workload(bad):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    args = ["--seed", "1", "--seconds", "1"] + bad
    p = subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
