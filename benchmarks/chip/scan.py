"""Which worlds of a cell the program runs as the reference does.

    python3 benchmarks/chip/scan.py --workload <name> --draw <seed> --worlds <n>

Draws ``n`` worlds (seeds of ``run_spec``), each
``random.Random(draw).randrange(2**31)`` in turn. In one process, after
the cell's set-up, runs the program on each at the cell's size and holds
it to the float64 reference under the cell's limits. Prints one JSON
line per world that departs, then the count and the worlds that hold:
the pool a traffic's ``worlds`` lists, whether it is that list, and each
number's largest reading over the pool, with its world. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import random
import sys

import run
from reference import reference_run

import compare


def drawn(draw: int, n: int) -> list[int]:
    rng = random.Random(draw)
    return [rng.randrange(2 ** 31) for _ in range(n)]


def scan(cell: run.Cell, worlds: list[int]):
    """Yield ``(world, checks)`` for each world: the program's run at the
    cell's size held to the reference, each number beside its limit."""
    from repro.core import ScenarioSpec
    from repro.launch.experiments import run_spec

    spec, n = cell.spec_dict(), cell.traffic["n_jobs"]
    prog = ScenarioSpec.from_dict(spec)
    for world in worlds:
        kept: list = []
        with run.kept_results(kept):
            r = run_spec(prog, seed=world, n_jobs=n)
        got = compare.readings(kept[0], run.reference_side(
            reference_run(spec, world, n)))
        if spec["net"] == "device":
            got["flush_on_host"] = float(r.net_stats["flush_host"])
        yield world, run.beside_limits(cell, got)


def pool_of(cell: run.Cell, worlds: list[int]) -> dict:
    """The worlds that depart (each with its numbers), the pool of those
    that hold, and each number's largest reading over the pool with its
    world."""
    departed, pool, worst = [], [], {}
    for i, (world, checks) in enumerate(scan(cell, worlds)):
        values = {k: c["value"] for k, c in checks.items()}
        if not run.is_correct(checks):
            departed.append({"world": world, "index": i, **values})
            continue
        pool.append(world)
        for k, v in values.items():
            if k not in worst or v > worst[k][0]:
                worst[k] = [v, world]
    return {"departed": departed, "pool": pool, "pool_worst": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--draw", type=int, required=True)
    ap.add_argument("--worlds", type=int, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    print(json.dumps(run.set_up(cell)), flush=True)
    worlds = drawn(args.draw, args.worlds)
    found = pool_of(cell, worlds)
    for row in found["departed"]:
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "draw": args.draw,
                      "worlds": len(worlds),
                      "departed": len(found["departed"]),
                      "pool_is_traffic":
                          found["pool"] == cell.traffic.get("worlds"),
                      "pool_worst": found["pool_worst"],
                      "pool": found["pool"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
