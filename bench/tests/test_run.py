"""The command refuses to measure anything but the chip: off a TPU, and
in a checkout that holds only the benchmark, it exits non-zero and
prints nothing on standard output."""

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "vdit-gen512-ripple", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "not 'tpu'" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
