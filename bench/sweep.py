"""Find an open-loop cell's knee: the highest offered rate the engine keeps
up with.  Not part of a benchmark run; run once on the chip when a cell's
rate is chosen, and write the result into PERF.md.

    python3 -m bench.sweep --workload minitron-4b.chat \
        --rates 0.6,0.8,1.0,1.2,1.4 --seconds 51 --seed 1

One process and one engine: the weights and the warm-up are paid once, then
each rate gets a window of ``--seconds`` and a drain.  Per rate it prints
the offered and completed request rates, the TTFT median and p90 (from when
each request was due), and how many requests were still waiting at the
window's close.  Past the knee the queue grows through the window, so the
waiting count and the TTFT tail jump together.
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
    ap.add_argument("--workload", required=True, action="append")
    ap.add_argument("--rates", required=True, action="append",
                    help="comma-separated offered rates; one per --workload")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import numpy as np
    from bench import harness, traffic
    from bench.drivers.serve import Calls, ServeCell

    harness.enable_cache(ROOT)
    cell = None
    for wl, rates in zip(args.workload, args.rates):
        spec = harness.load_spec(ROOT, wl)
        harness.check_devices(spec.cell["chips"], True)
        if cell is None:
            cell = ServeCell(spec, args.seed, args.seconds)
            cell.setup()
            print(f"[sweep] set-up {time.perf_counter() - T_START:.1f}s",
                  flush=True)
        for rate in (float(r) for r in rates.split(",")):
            cell.mix = dict(spec.traffic, rate_per_s=rate)
            cell.items = traffic.generate(cell.mix,
                                          spec.model_cfg["vocab_size"],
                                          args.seed, args.seconds)
            cell.served, cell.finished, cell.calls = [], {}, Calls()
            t0, t1 = cell.window()
            waiting = len(cell.engine.queue)
            cell.drain()
            reqs = cell.window_requests()
            ttft = [1e3 * (r.t_first - r.due) for r in reqs
                    if r.t_first is not None]
            tpot = [1e3 * (r.t_done - r.t_first) / (len(r.result) - 1)
                    for r in reqs if r.result is not None
                    and len(r.result) > 1]
            done = sum(r.t_done is not None and r.t_done <= t1 for r in reqs)
            print(json.dumps({
                "workload": wl, "mix": spec.cell["traffic"], "offered_per_s": rate,
                "requests": len(reqs),
                "completed_in_window_per_s": done / (t1 - t0),
                "waiting_at_close": waiting,
                "ttft_p50_ms": float(np.percentile(ttft, 50)),
                "ttft_p90_ms": float(np.percentile(ttft, 90)),
                "tpot_p90_ms": float(np.percentile(tpot, 90)),
                "drain_s": cell.t_drained - t1,
                "unserved": sum(r.result is None for r in reqs)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
