"""Digests of the dense bidirectional model's outputs at the program's
``llada-8b-tiny`` preset, one JSON line on stdout: the forward's logits,
the cache capture's K/V and a cached window step's logits.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/pinned_llada.py

It keeps to one CPU core (XLA:CPU splits a matmul's sums by its thread
count, so the bits depend on the cores it gets);
``test_moe_block.py`` compares the digests with the ones the program
gave before the block-causal mask and the dropless expert layer.
"""
import hashlib
import json
import os
import sys

# before JAX starts: one core, so one split of every sum
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models.model import (capture_cache, forward,  # noqa: E402
                                forward_cached, init_model)


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def main() -> int:
    cfg = get_config("llada-8b-tiny")
    params = init_model(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(7)
    toks = rs.randint(0, cfg.mask_token_id, (2, 32)).astype(np.int32)
    toks[:, 20:] = cfg.mask_token_id
    toks = jnp.asarray(toks)
    state = capture_cache(params, toks, cfg)
    out = {"forward": digest(forward(params, toks, cfg)[0]),
           "capture": digest(*jax.tree.leaves(state.layer_states)),
           "cached": digest(forward_cached(params, toks[:, 16:24],
                                           jnp.int32(16), state, cfg))}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
