"""Readings for a cell's limits: the program's and the float8 control's.

    python3 -m bench.calibrate --workload <cell> --seeds 11,12,13 \\
        --seconds 10 [--control-seeds 11,12]

Runs the cell once per seed in this one process (set-up, a window of
``--seconds`` at the cell's own load, the reference replay), and for
the control seeds also reads the float8 control at the same positions.
One JSON line per seed: the compared numbers and ``correct`` under the
current limits, and for a control seed the control's compared numbers
and whether they would pass (they must not).  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from bench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        spec, cell, config, mix, limits = run.load_cell(args.workload)
        devices = run.require_accelerator(int(cell["chips"]))
    except (run.SetupError, OSError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(spec, args.workload, config, mix, limits, seed,
                           args.seconds, False, devices,
                           control=seed in controls)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "compared": res["compared"], "control": res.get("control"),
            "metrics": res["metrics"], "attempted": res["attempted"],
            "failed": res["failed"], "device": res["device"],
            "diagnostics": res["diagnostics"]}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
