"""Readings for the limits of a cell's output comparison, many seeds in one
process: what sound runs of the program give and what the control (the next
precision down) gives, against the plain reference.  Not part of a
benchmark run.  ``--write`` turns the readings into the cell's limits by the
runner's rule and writes them to ``limits/<workload>.json``.

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 [--first-seed n] [--write]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import core, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.seconds, args.trace = args.first_seed, 0.0, 0

    cell, config, traffic = core.find_cell(core.load_manifest(), args.workload)
    if args.rehearse:
        config, traffic = run.rehearsal_overrides(traffic)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    run.enable_cache()
    ctx = run.Context(args, cell, config, traffic)
    ctx.log("device", **run.device_facts())
    runner = core.load_runner(traffic["runner"])
    # seeds spread out: the driver's are large and unrelated
    readings = runner.calibrate(
        ctx, [args.first_seed + 7919 * i for i in range(args.seeds)],
        args.control_seeds)
    if args.write:
        limits = dict(runner.limits_from(readings), device=run.device_facts())
        path = core.limits_path(args.workload, args.rehearse)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(limits, f, indent=1)
            f.write("\n")
        ctx.log("limits", path=os.path.relpath(path, core.ROOT), **limits)
    return 0


if __name__ == "__main__":
    sys.exit(main())
