"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

* busy: the union of the intervals in which a program ran on a device,
  inside the window, averaged over the devices;
* device time by program name (``jit_`` and numeric suffixes stripped), and
  by operation for the collectives;
* idle gaps: the complement of busy inside the window, each attributed to
  the innermost of the harness's own host spans (``bench.*``) that holds
  the gap's midpoint; ``idle_gaps`` sums them by span, longest first.

The window is the ``bench.window`` host span.  ``reduce_events`` is the
reduction itself, over plain event lists, so that it is tested without a
chip; ``load_xspace`` reads those lists from a trace file.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


def program_name(raw: str) -> str:
    """'jit_tick(123)' / 'jit_chunk.4' / 'jit_tick' -> 'tick'."""
    name = re.sub(r"\(\d+\)$", "", raw.strip())
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(events: Sequence[Event], w0: float, w1: float):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def reduce_events(devices: Dict[str, Dict[str, Sequence[Event]]],
                  host: Sequence[Event], window: Optional[Tuple[float, float]]
                  = None, top: int = 10) -> Dict:
    """``devices``: device name -> {"programs": events, "ops": events}.
    ``host``: the harness's host spans.  Times in ns; results in s."""
    if window is None:
        spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = max(spans, key=lambda w: w[1] - w[0])
    w0, w1 = window
    inner = [(n, s, s + d) for n, s, d in host
             if n.startswith(HOST_PREFIX) and n != WINDOW_SPAN]
    by_program: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    collectives = 0.0
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    idle_by: Dict[str, float] = {}
    for dev, lines in devices.items():
        progs = list(_clip(lines.get("programs", ()), w0, w1))
        for name, a, b in progs:
            p = program_name(name)
            by_program[p] = by_program.get(p, 0.0) + (b - a) * 1e-9
            counts[p] = counts.get(p, 0) + 1
        for name, a, b in _clip(lines.get("ops", ()), w0, w1):
            if COLLECTIVE.search(name):
                collectives += (b - a) * 1e-9
        busy = union((a, b) for _, a, b in progs)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            holding = [(e - s, n) for n, s, e in inner if s <= mid < e]
            label = min(holding)[1] if holding else "none"
            label = label[len(HOST_PREFIX):] if label != "none" else label
            gaps.append((label, (b - a) * 1e-9))
            idle_by[label] = idle_by.get(label, 0.0) + (b - a) * 1e-9
    n = max(1, len(devices))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n,
        "devices": len(devices),
        "program_s": {k: v / n for k, v in by_program.items()},
        "program_count": {k: v // n for k, v in counts.items()},
        "collective_s": collectives / n,
        "idle_by_host_span_s": {k: v / n for k, v in idle_by.items()},
        "idle_gaps": sorted(([k, v / n] for k, v in idle_by.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": [[k, v] for k, v in gaps[:top]],
        "device_ops": sorted(([k, v / n] for k, v in by_program.items()),
                             key=lambda kv: -kv[1])[:top],
    }


def _events(line) -> List[Event]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load_xspace(path: str, want_ops: bool = False
                ) -> Tuple[Dict[str, Dict[str, List[Event]]], List[Event]]:
    """Device program (and, on request, op) events and the harness's host
    spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    lines["programs"] = _events(line)
                elif line.name == "XLA Ops" and want_ops:
                    lines["ops"] = _events(line)
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e[0].startswith(HOST_PREFIX)]
    return devices, host


__all__ = ["reduce_events", "load_xspace", "union", "program_name"]
