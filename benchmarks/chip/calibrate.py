"""Readings that the limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds <n> ...

In one process, after the cell's set-up: for each seed, one whole run of
the program at the cell's size compared with the float64 reference (the
lower readings), and the reference computed in the configuration's
``control_precision`` put in the program's place (the control's readings,
which the limits must fail). Prints one JSON line per seed and side, then
per number the largest sound reading and the smallest control reading.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from reference import reference_run

import compare


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    spec, n = cell.spec_dict(), cell.traffic["n_jobs"]
    device = run.set_up(cell)
    from repro.core import ScenarioSpec
    from repro.launch.experiments import run_spec

    prog = ScenarioSpec.from_dict(spec)
    reference = {seed: run.reference_side(reference_run(spec, seed, n))
                 for seed in args.seeds}
    sound, control = [], []
    for seed in args.seeds:
        kept: list = []
        with run.kept_results(kept):
            r = run_spec(prog, seed=seed, n_jobs=n)
        got = compare.readings(kept[0], reference[seed])
        if spec["net"] == "device":
            got["flush_on_host"] = float(r.net_stats["flush_host"])
        sound.append(got)
        print(json.dumps({"side": "program", "seed": seed, "device": device,
                          **got}), flush=True)
    for seed in args.seeds:
        got = compare.readings(run.reference_side(reference_run(
            spec, seed, n, precision=cell.config["control_precision"])),
            reference[seed])
        control.append(got)
        print(json.dumps({"side": "control", "seed": seed, **got}),
              flush=True)
    summary = {k: {"lower": max(r[k] for r in sound),
                   "control_min": min(r[k] for r in control),
                   "limit": cell.limits[k]} for k in compare.NUMBERS}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
