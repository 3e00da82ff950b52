"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload minitron-4b.chat --seed 7 \
        --seconds 40 --trace 0

From the root of a checkout.  It sets up (weights from the seed, programs
from the compile cache in ``<checkout>/.jax_cache``, warm-up), measures for
``--seconds``, checks the served tokens against the plain reference, and
prints the checks on standard error and one JSON object as the last line of
standard output.  ``--trace 1`` reports the cell's per-layer metrics from a
profiler trace of the window instead of its end-to-end ones.  It exits
non-zero, printing no result, without a TPU or with fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"bench: the system under test is not here ({src}/repro)")
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(ROOT))
    from bench.harness import run_cell
    res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
