#!/usr/bin/env python3
"""Read the control of a cell on the chip, or a fault planted in the
program: driven through the cell's own traffic and compared exactly as a
run of the program is.  Their readings are the far ends that the limits of
``correct`` are set against; each must come out as not correct.

* ``control`` — the reference search put in the program's place one
  precision below the configuration's (bfloat16 for its float32);
* ``short_search`` — the program, its search cut to an eighth of its hops
  (``bench.system.ShortSearch``);
* ``drop_best`` — the program, each answer without its best candidate
  (``bench.system.DropBest``);
* ``program`` — the program itself, for the sound readings.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --system control short_search --seeds 1 2 3

One process reads every seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run, spec  # noqa: E402
from bench.system import (Control, DropBest, Program,  # noqa: E402
                          ShortSearch)

SYSTEMS = {"program": Program, "control": Control,
           "short_search": ShortSearch, "drop_best": DropBest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--system", choices=sorted(SYSTEMS), nargs="+",
                    default=["control"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    for name in args.system:
        for seed in args.seeds:
            res = run.run_cell(bench, cell, seed=seed, seconds=args.seconds,
                               trace=False, system=SYSTEMS[name],
                               t_start=time.perf_counter())
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "system": name, "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
