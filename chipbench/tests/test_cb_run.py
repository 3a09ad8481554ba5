"""The runner refuses to run without a TPU: it exits nonzero before any
set-up and prints no result."""
import os
import subprocess
import sys

from chipbench.registry import ROOT


def test_runner_exits_nonzero_with_no_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "cohere768-1m.batch64", "--seed", str(2 ** 31 + 7), "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
    assert "data:" not in p.stderr          # no set-up began


def test_runner_refuses_an_unknown_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "no.such",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
