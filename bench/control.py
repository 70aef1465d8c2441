"""Readings of the numbers ``correct`` compares, over many seeds in one
process: of the program as the configuration states it, and of the control.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--units N] [--control]

The control is the program with the one guarantee the configuration states
switched off that a later change might be tempted to drop: Hanoi's
majority-path-first order (paper SS VII-C), through the program's own
``majority_first=False`` path.  The reference keeps the stated order, so a
sound comparison must find the control's results wrong.  Without
``--control`` the same units run as the configuration states them: those
readings are the lower ones the limits are set from.

Each seed runs ``N`` units through the cell's own entry, at the cell's own
sizes, after one warm-up unit: by default as many as a run checks (a mix
that checks every unit of its window needs ``--units``).  Each seed prints
one JSON line of readings; the last line gathers them all.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.check import Checker  # noqa: E402
from bench.generator import Mix  # noqa: E402


def readings(workload: str, seeds, units: "int | None", control: bool,
             root: str = ROOT) -> dict:
    spec = harness.cell_spec(harness.load_manifest(root), workload)
    cell = spec["cell"]
    config = harness.load_json(os.path.join(root, spec["config"]["file"]))
    mix_data = harness.load_json(os.path.join(root, "bench", "traffic",
                                              f"{cell['traffic']}.json"))
    from repro.engine import install_jax_cache
    install_jax_cache()
    device = harness.require_device(int(cell["chips"]))
    mix = Mix(config, mix_data, os.path.join(root, "bench"),
              majority_first=False if control else None)
    if units is None:
        if mix_data["check_units"] == "all":
            raise SystemExit("the mix checks every unit of a window: give "
                             "--units, the number a run completes")
        units = int(mix_data["check_units"])
    out = {"workload": workload, "control": control, "device": device,
           "units": units, "readings": {}}
    harness.run_unit(mix, seeds[0], 0)          # compiles every shape
    for seed in seeds:
        checker = Checker(mix)
        totals = {"warps_differing": 0, "cells_differing": 0, "warps": 0}
        for k in range(1, units + 1):
            got = checker.check(harness.run_unit(mix, seed, k))
            for key in totals:
                totals[key] += got[key]
        out["readings"][str(seed)] = totals
        print(json.dumps({"seed": seed, **totals}), flush=True)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--units", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    print(json.dumps(readings(args.workload, seeds, args.units,
                              args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
