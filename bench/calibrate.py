"""Readings that a cell's ``max_logit_gap`` limit is set from.  Not part of a
benchmark run; run on the chip when a cell or its limit is set, and write
the readings and the limit into PERF.md.

    python3 -m bench.calibrate --workload minitron-4b.chat \
        --seeds 101,102,103 --control-seeds 3 --seconds 51

One process and one warm engine at the cell's own size and load: for each
seed, the seed's weights and traffic, a window, the drain, and the same
sample the benchmark compares, read against the f32 reference (the
program's reading) and, on the first ``--control-seeds`` seeds, with the
fp8 control in the program's place (the control's reading: the gap of the
token the fp8 forward puts first).  Each reading goes through the cell's
own checks, so the line says ``correct`` for the program and
``control_correct`` for the control as a benchmark run would.  The
reference runs beside the engine here, which the benchmark's own runs never
do.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first this many seeds")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness

    harness.enable_cache(ROOT)
    spec = harness.load_spec(ROOT, args.workload)
    harness.check_devices(spec.cell["chips"], True)
    cell = None
    seeds = [int(s) for s in args.seeds.split(",")]
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        if cell is None:
            cell = spec.driver(seed, args.seconds)
            cell.setup()
        else:
            cell.reseed(seed)
        cell.window()
        cell.drain()
        control = n < args.control_seeds
        cmp = cell.compare(control, release=False)
        row = {"workload": args.workload, "seed": seed,
               "correct": harness.verdict(cell.checks(cmp, False))}
        if control:
            row["control_correct"] = harness.verdict(cell.checks(cmp, True))
        row.update(cmp)
        row["attempted"], row["unserved"] = cell.tally()
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
