"""Record the small device trace that test_trace_reduce.py reads.

    python3 -m bench.tests.record_trace <out.xplane.pb>

On one TPU chip: a jitted bf16 matmul and the program's fused
confidence kernel, each called three times inside one profiler window.
It prints the planes, lines and the first device ops, so a reader can
see how the chip names them.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from repro.kernels.confidence import confidence_fused
    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU", file=sys.stderr)
        return 2
    mm = jax.jit(lambda a, b: (a @ b).astype(jnp.float32))
    conf = jax.jit(lambda x: confidence_fused(x, interpret=False))
    a = jnp.ones((512, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 4096), jnp.bfloat16)
    jax.block_until_ready(conf(mm(a, b)))
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        jax.block_until_ready(conf(mm(a, b)))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, sys.argv[1])
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12])
        if plane.name.startswith("/device:TPU:0"):
            for ln in plane.lines:
                for ev in list(ln.events)[:12]:
                    print("  ", ln.name, "|", ev.name, ev.start_ns,
                          ev.duration_ns, [(k, v) for k, v in ev.stats][:8])
    shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
