"""Digests of the seeded weights and of the reference's outputs for the
dense GQA family at the program's ``llada-8b-tiny`` widths, one JSON
line on stdout.

    JAX_PLATFORMS=cpu python -m bench.tests.pinned

It keeps to one CPU core (XLA:CPU splits a matmul's sums by its thread
count, so the bits depend on the cores it gets); ``test_pinned.py``
compares the digests with the ones this harness gave before the
family's code moved into ``bench/families/``.
"""
import hashlib
import json
import os
import sys

# before JAX starts: one core, so one split of every sum
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference, run, weights  # noqa: E402
from repro.configs import get_config  # noqa: E402

SEED = 2**31 + 1234


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def main() -> int:
    fam = run.load_family("dense_gqa")
    t = get_config("llada-8b-tiny")
    sizes = fam.tiny_sizes({"rope": "standard", "rope_theta": 500000.0,
                            "norm_eps": 1e-06}, t)
    out = {}
    for dt in ("bfloat16", "float32"):
        flat = weights.flatten(weights.make_params(
            fam.param_shapes(sizes, 2), SEED, jnp.dtype(dt)))
        out["params_" + dt] = digest(*(k.encode() for k in sorted(flat)),
                                     *(flat[k] for k in sorted(flat)))
    flat = weights.flatten(weights.make_params(fam.param_shapes(sizes, 2),
                                               SEED, jnp.bfloat16))
    rs = np.random.RandomState(7)
    mask = sizes["mask_token_id"]
    tokens = rs.randint(0, mask, size=(2, 32)).astype(np.int32)
    tokens[:, 20:] = mask
    tok = jnp.asarray(tokens)
    out["forward_rows"] = digest(fam.forward_rows(flat, tok, 16, sizes, 8))
    out["forward_rows_fp8"] = digest(
        fam.forward_rows(flat, tok, 16, sizes, 8, True))
    kv = fam.capture(flat, tok, sizes)
    out["capture"] = digest(*kv)
    out["forward_window"] = digest(
        fam.forward_window(flat, tok[:, 16:24], 16, kv, sizes))
    prompts = rs.randint(0, mask, size=(2, 16)).astype(np.int32)
    served = rs.randint(0, mask, size=(2, 16)).astype(np.int32)
    served[1, 3] = mask
    for policy in ("none", "dual"):
        geo = {"gen_length": 16, "block_size": 8, "steps": 8,
               "cache_policy": policy}
        g, cg = reference.replay(fam, flat, sizes, prompts, served, geo,
                                 mask, True)
        out["replay_" + policy] = digest(g, cg)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
