"""Arithmetic the readers of the serving program's own records share.

``ContinuousEngine`` keeps its counters in ``EngineTelemetry``: the harness
takes ``snapshot()`` at the window's edges (``counters0``/``counters1``),
which holds ``steps``, ``prefill_calls``, ``prefills_completed`` and, per
host span phase, ``phase_s.<phase>`` seconds (``serve.step``,
``serve.tick.wait``, ...).  ``telemetry.requests`` holds one record per
retired or shed request, with its admission time.  A program that keeps
none of these reads ``None``, never an error.
"""

from __future__ import annotations

from typing import List, Optional

BLOCKING = (".wait", ".sync")     # phases that block on a device read
# ``serve.prefill`` dispatches a step's chunks back to back, and each
# dispatch returns only once the chunk before it has run: its time is the
# device's, so it counts as blocked too.
DEVICE_BOUND = ("phase_s.prefill",)


def delta(run, key: str) -> Optional[float]:
    """A counter's growth over the window (None if the program has none)."""
    c0 = getattr(run.cell, "counters0", None) or {}
    c1 = getattr(run.cell, "counters1", None) or {}
    if key not in c1:
        return None
    return c1[key] - c0.get(key, 0)


def host_ms_per_step(run) -> Optional[float]:
    """Milliseconds of each engine step the host spent on its own work: the
    ``step`` span's seconds less those of every ``.wait``/``.sync`` span
    and of the ``prefill`` span (``DEVICE_BOUND``), over the window's
    steps."""
    steps, step_s = delta(run, "steps"), delta(run, "phase_s.step")
    if not steps or step_s is None:
        return None
    keys = getattr(run.cell, "counters1", {})
    blocked = sum(delta(run, k) for k in keys if k in DEVICE_BOUND or (
        k.startswith("phase_s.") and k.endswith(BLOCKING)))
    return 1e3 * (step_s - blocked) / steps


def prefill_pieces(run) -> Optional[float]:
    """Engine steps per completed prefill in the window: the prefill calls
    over the prefills that finished."""
    calls, done = delta(run, "prefill_calls"), delta(run, "prefills_completed")
    if calls is None or not done:
        return None
    return calls / done


def queue_wait_ms(run) -> Optional[List[float]]:
    """Milliseconds each request due in the window waited from when it was
    due to its admission (the engine's per-request records, by ``rid``); a
    request with no record of an admission counts with its wait up to the
    end of the drain, as ``readers.ttft_ms`` counts one never served."""
    c = run.cell
    records = getattr(getattr(getattr(c, "engine", None), "telemetry", None),
                      "requests", None)
    if records is None:
        return None
    admitted = {r.rid: r.t_admit for r in records}
    end = getattr(c, "t_drained", c.t1)
    waits = []
    for s in c.window_requests():
        t = admitted.get(s.rid)
        waits.append(1e3 * ((end if t is None else t) - s.due))
    return waits
