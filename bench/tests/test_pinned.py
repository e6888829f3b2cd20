"""The dense GQA family reproduces, bit for bit, the seeded weights and
the reference's outputs that the harness gave before its
architecture-dependent code moved into ``bench/families/dense_gqa.py``
(recorded on one CPU core with ``bench/tests/pinned.py``)."""
import json
import os
import subprocess
import sys

from bench import run

PINNED = {"params_bfloat16": "3f555ed791ebe770",
          "params_float32": "271be0c8ba64b6c4",
          "forward_rows": "9d42841a794270c7",
          "forward_rows_fp8": "9addc76b033d564b",
          "capture": "76f5515932ad0ad9",
          "forward_window": "a744197303ff6875",
          "replay_none": "f5e6dfdaa186315c",
          "replay_dual": "038273bd5443ca5e"}


def test_weights_and_reference_as_pinned():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    src = os.path.join(run.ROOT, "src")
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [src, run.ROOT] + [p for p in [env.get("PYTHONPATH")] if p]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.pinned"], cwd=run.ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == PINNED
