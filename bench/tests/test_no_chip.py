"""Without a TPU the benchmark refuses to run and prints no result."""
import os
import subprocess
import sys

from bench import run


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "llada8b-fdma-offline", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=run.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout and '"metrics"' not in out.stdout
