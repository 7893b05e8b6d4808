"""Read a cell's compared numbers over many seeds in one process: the
program as it is, or with the control or a fault planted.

    python3 -m bench.control --workload <cell> --seconds <s> \
        --plant <none|stale_snapshot|...> --seeds <n> [<n> ...]

Runs on the chip only, like ``bench.run``.  One process pays one start
and one compilation, then one load and one short window per seed.  Each
run prints one JSON line: the seed, the plant, ``correct`` and every
compared number beside its limit.  The benchmark's own runs never plant
anything; this is how the limits' lower readings (``--plant none``) and
upper readings (``--plant stale_snapshot``) are taken.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import faults, spec
from bench.run import chip_peaks, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plant", default="none",
                    choices=["none"] + sorted(faults.PLANTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    peaks = chip_peaks(cell)
    if peaks is None:
        return 1
    plant = faults.PLANTS.get(args.plant)
    for seed in args.seeds:
        r = run_cell(cell, seed, args.seconds, False, peaks=peaks,
                     t_start=time.perf_counter(), plant=plant)
        print(json.dumps({"seed": seed, "plant": args.plant,
                          "correct": r["correct"], "metrics": r["metrics"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
