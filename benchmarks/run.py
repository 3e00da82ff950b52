"""Benchmark harness entrypoint — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only name]

Prints ``name,us_per_call,derived`` CSV rows and writes machine-readable
``results/bench/BENCH_<stem>.json`` trajectory files (benchmarks/common.py).
The sort benchmarks share the ``sort`` stem: ``BENCH_sort.json`` carries the
before/after rows the perf trajectory tracks.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing results/bench/BENCH_*.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (all_scan, fannkuch, find_first, moe_dispatch, recovery,
                   roofline, scan_ssm, serve_load, slo_load, sort_adaptors,
                   sort_compare, task_counts)
    from .common import header, reset, write_json

    # module name -> (module, JSON stem); sort benches share one trajectory
    modules = {
        "find_first": (find_first, "find_first"),        # paper Fig. 3/4
        "all_scan": (all_scan, "all_scan"),              # paper Fig. 5
        "sort_adaptors": (sort_adaptors, "sort"),        # paper Fig. 6
        "sort_compare": (sort_compare, "sort"),          # paper Fig. 7
        "fannkuch": (fannkuch, "fannkuch"),              # paper Fig. 8
        "task_counts": (task_counts, "task_counts"),     # §2.1 / §3.6 claims
        "moe_dispatch": (moe_dispatch, "moe_dispatch"),  # sort dispatch
        "roofline": (roofline, "roofline"),              # §Roofline summary
        "recovery": (recovery, "recovery"),              # fault recovery cost
        "serve_load": (serve_load, "serve"),             # continuous batching
        "slo_load": (slo_load, "slo"),                   # SLO degradation
        "scan_ssm": (scan_ssm, "scan_ssm"),              # chunked SSM scan
    }
    header()
    failed = []
    # group modules by stem so shared trajectories land in one file
    by_stem: dict = {}
    for name, (mod, stem) in modules.items():
        if args.only and name != args.only:
            continue
        by_stem.setdefault(stem, []).append((name, mod))
    for stem, mods in by_stem.items():
        reset()
        ran_any = False
        for name, mod in mods:
            try:
                mod.run()
                ran_any = True
            except Exception:
                failed.append(name)
                traceback.print_exc()
        if ran_any and not args.no_json:
            path = write_json(stem)
            print(f"# wrote {path}", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
