"""The one traffic generator: reads a mix's parameters, draws requests.

A mix file (``bench/traffic/<name>.json``) names its ``kind``:

* ``open_loop`` — requests due at fixed times, whatever the server does:
  ``rate_per_s`` and ``arrivals`` (``poisson``: exponential gaps);
* ``backlog`` — a standing queue of ``queue_depth`` requests from t=0,
  topped up as the server admits them;
* ``batches`` — training steps of ``batch`` rows of ``seq`` tokens
  (``batch_tokens``).

and the sizes, each ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}``: ``prompt`` and
``max_new``, in tokens.  Prompt tokens are drawn uniformly from
``[token_min, vocab)``.

Every seed gets the same requests' sizes and the same gaps, in another
order: the sizes and gaps are the quantiles of their distributions at
evenly spaced points, prompt and output lengths paired the same way for
every seed, and the seed only permutes them and draws the token ids.  So runs
with different seeds do the same amount of work, and their spread is the
system's, not the draw's.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Item:
    due_s: float             # offset from the window's start (0 for backlog)
    prompt: np.ndarray       # (S,) int32
    max_new: int


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    """n values at evenly spaced quantiles of ``spec``, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def _gaps(mix: Dict, n: int) -> np.ndarray:
    """n gaps of mean 1/rate: exponential quantiles for poisson arrivals."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    if n <= 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.mean() / mix["rate_per_s"]


def request_count(mix: Dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, int(math.floor(mix["rate_per_s"] * seconds)))
    return mix["pool"]


def generate(mix: Dict, vocab: int, seed: int, seconds: float) -> List[Item]:
    """The requests of one run.  Open loop: the first is due at 0 and every
    one inside the window (n - 1 gaps sum to (n - 1)/rate < seconds).
    Backlog: a pool served in order, due at 0."""
    n = request_count(mix, seconds)
    # the pairs of sizes are fixed (a seed-independent shuffle pairs the
    # quantiles); the seed permutes the pairs
    pairing = np.random.default_rng(0).permutation(n)
    order = np.random.default_rng(seed).permutation(n)
    rng = np.random.default_rng([seed, 1])
    prompts = _quantiles(mix["prompt"], n)[order]
    news = _quantiles(mix["max_new"], n)[pairing][order]
    if mix["kind"] == "open_loop":
        gaps = rng.permutation(_gaps(mix, n - 1))
        due = np.concatenate([[0.0], np.cumsum(gaps)])
    elif mix["kind"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    lo = mix.get("token_min", 3)
    return [Item(float(due[i]),
                 rng.integers(lo, vocab, int(prompts[i]), dtype=np.int32),
                 int(news[i]))
            for i in range(n)]


def batch_tokens(mix: Dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """Training mix (``kind`` ``batches``): step ``step``'s token rows,
    ``(batch, seq + 1)`` int32 drawn from ``[token_min, vocab)`` (inputs
    and their next-token targets).  The same seed and step give the same
    rows; every step and row differ."""
    if mix["kind"] != "batches":
        raise ValueError(f"not a training mix: {mix['kind']!r}")
    rng = np.random.default_rng([seed, 2, step])
    return rng.integers(mix.get("token_min", 3), vocab,
                        (mix["batch"], mix["seq"] + 1), dtype=np.int32)


def prompt_lengths(mix: Dict, seconds: float) -> List[int]:
    """Every distinct prompt length the mix can send (seed-independent)."""
    n = request_count(mix, seconds)
    return sorted(set(int(v) for v in _quantiles(mix["prompt"], n)))


__all__ = ["Item", "generate", "batch_tokens", "prompt_lengths",
           "request_count"]
