"""Fix a configuration's depth ahead of time, for a described TPU v5e.

    JAX_PLATFORMS=cpu python -m bench.depth <config> [--max N]

For each depth from ``--max`` down, the served block programs of every
cell of the configuration (its batch, each prompt length its mix draws)
and the weight builder are compiled for one v5e chip that is described,
not attached, and ``memory_analysis`` gives their bytes.  The depth is
the largest whose largest program leaves 2 GB of the chip's memory
spare.  Nothing runs, so no chip is needed; the result is written into
the configuration's file by hand, with the bytes printed here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# peak_bytes_in_use's limit on one v5e chip (bytes_limit, chip run PR 11)
BYTES_LIMIT = 16_909_336_064
SPARE = 2 * 10**9


def program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


def cells_of(config: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    return [w for w in spec["workloads"] if w["config"] == config]


def measure(config: dict, mixes: list, depth: int, one_chip) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import weights
    from repro.configs import DecodeConfig
    from repro.core import Decoder

    family = run.load_family(config["family"])
    cfg = family.program_config(dict(config, depth=depth))
    flat = {k: jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16,
                                    sharding=one_chip)
            for k, leaf in family.param_shapes(config["sizes"],
                                               depth).items()}
    params = weights.unflatten(flat)
    need = {}
    for mix in mixes:
        dcfg = DecodeConfig(**mix["decode"])
        for lp in sorted(int(x) for x in mix["prompt_lengths"]):
            with jax.default_device(one_chip._device):
                lowered = Decoder(params, cfg, dcfg).lower_blocks(
                    mix["max_batch"], lp)
            for part, low in lowered.items():
                name = f"{mix['decode']['strategy']}/" \
                       f"{mix['decode']['cache_policy']}/b{mix['max_batch']}" \
                       f"/p{lp}:{part}"
                try:
                    need[name] = program_bytes(low.compile())
                except jax.errors.JaxRuntimeError as e:
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        raise
                    need[name] = None
    return need


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("config")
    ap.add_argument("--max", type=int, default=32)
    ap.add_argument("--min", type=int, default=1)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import traffic
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    config = run.load_config(args.config)
    mixes = [traffic.load_mix(c["traffic"]) for c in cells_of(args.config)]
    for depth in range(args.max, args.min - 1, -1):
        need = measure(config, mixes, depth, one_chip)
        worst = max(need.values(), key=lambda v: float("inf") if v is None
                    else v)
        ok = worst is not None and BYTES_LIMIT - worst >= SPARE
        print(json.dumps({"depth": depth, "fits": ok, "bytes": need,
                          "limit": BYTES_LIMIT}), flush=True)
        if ok:
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
