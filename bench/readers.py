"""Arithmetic the metric readers share (``bench/metrics/<name>.py``); a
quantity that several cells report under names of their own has its reader
here, and each name's file binds it.

Program names are the system's jitted functions as the trace shows them:
``chunk`` is one by_blocks prefill chunk (``serve/prefill.py``).  The
decode tick of ``decode_tick`` steps (``serve/early_exit.py``) is jitted
from a ``functools.partial``, which has no name, so the trace shows it as
``jit__unknown``; it is the only unnamed program of the serving path.  The
reader takes ``tick`` too, for when the program names it.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

PREFILL_PROGRAMS = ("chunk",)
DECODE_PROGRAMS = ("tick", "_unknown")


def p90(values) -> Optional[float]:
    v = [x for x in values if x is not None]
    return float(np.percentile(v, 90)) if v else None


def ttft_ms(run):
    """Time to first token of every request due in the window, in ms, from
    when it was due (not when it was submitted) to its first token on the
    host; a request still unserved at the end of the drain counts with its
    wait so far."""
    c = run.cell
    end = getattr(c, "t_drained", c.t1)
    return [1e3 * ((s.t_first if s.t_first is not None else end) - s.due)
            for s in c.window_requests()]


def window_calls(run):
    c = run.cell
    return c.calls.between(c.t0, c.t1)


def program_s(run, names) -> Optional[float]:
    """Device seconds of the named programs in the traced window (None if
    none ran)."""
    if run.trace is None:
        return None
    s = sum(run.trace["program_s"].get(n, 0.0) for n in names)
    return s if s > 0 else None


def prefill_totals(run):
    """(real prompt tokens, needed FLOPs) of the prefill calls in the
    window."""
    m = run.spec.model_cfg
    toks = flops = 0
    for _, _, length, start, stop in window_calls(run).prefill:
        toks += max(0, min(stop, length) - start)
        flops += run.flops.prefill_flops(m, length, start, stop)
    return toks, flops


def decode_totals(run):
    """Summed decode_tick() readings (flops, bytes, steps) and the tick
    count of the window."""
    m = run.spec.model_cfg
    tot = {"flops": 0, "bytes": 0, "steps": 0, "least_s": 0.0}
    ticks = window_calls(run).ticks
    for _, _, lanes in ticks:
        t = run.flops.decode_tick(m, lanes)
        for k in ("flops", "bytes", "steps"):
            tot[k] += t[k]
        if run.peak:
            tot["least_s"] += max(t["bytes"] / run.peak["hbm_bytes_per_s"],
                                  t["flops"] / run.peak["bf16_flops_per_s"])
    return tot, len(ticks)


def idle_share(run) -> Optional[float]:
    """Share of the traced window in which no program ran on the device, in
    %."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def decode_step_ms(run) -> Optional[float]:
    """Device milliseconds of the decode tick programs per decode step (a
    tick runs ``decode_tick`` steps) in the traced window."""
    s = program_s(run, DECODE_PROGRAMS)
    _, ticks = decode_totals(run)
    if s is None or ticks == 0:
        return None
    return 1e3 * s / (ticks * run.cell.ecfg.decode_tick)


def decode_roofline(run) -> Optional[float]:
    """The decode ticks' least time over their device time, in %.  Least
    time is the larger of least bytes over the HBM peak and needed FLOPs
    over the compute peak; least bytes are the weights and the head once per
    step with a live lane, and the K/V of live positions of live lanes
    only."""
    s = program_s(run, DECODE_PROGRAMS)
    tot, ticks = decode_totals(run)
    if s is None or ticks == 0:
        return None
    return 100.0 * tot["least_s"] / s


def decode_mfu(run) -> Optional[float]:
    """Needed decode FLOPs at live lengths over the decode ticks' device
    time times the chip's peak, in %."""
    s = program_s(run, DECODE_PROGRAMS)
    tot, ticks = decode_totals(run)
    if s is None or ticks == 0:
        return None
    return 100.0 * tot["flops"] / (s * run.peak["bf16_flops_per_s"])
