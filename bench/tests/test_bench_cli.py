"""The command refuses to measure where it cannot: without a TPU, and in a
checkout that holds only the benchmark and not the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
from harness import HERE, ROOT

ARGS = ["--workload", "bert-large-1b.sharp-b8", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    _no_result(p)
    assert "needs 1 TPU chip" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    _no_result(_run(tmp_path))


@pytest.mark.parametrize("bad", [["--workload", "no-such-cell"],
                                 ["--trace", "2"]])
def test_rejects_unknown_arguments(bad):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    argv = ARGS.copy()
    i = argv.index(bad[0])
    argv[i + 1] = bad[1]
    p = subprocess.run([sys.executable, "bench/run.py", *argv], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    _no_result(p)
