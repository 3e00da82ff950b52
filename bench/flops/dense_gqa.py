"""Operations and least bytes that a dense GQA decoder needs, from its
shapes alone.  What is needed, not what a program happens to compute: the
head counts at the one position whose logits are used in prefill, and the
attention and K/V reads cover live positions only.

A multiply-add is two operations.  Norms, rotary tables, activations and
softmax are left out (under 0.1% of the total at the published widths).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np


def _z(m: Dict) -> Dict[str, int]:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return dict(d=m["d_model"], H=m["num_heads"], KV=m["num_kv_heads"],
                hd=hd, ff=m["d_ff"], V=m["vocab_size"], L=m["num_layers"],
                bytes=np.dtype(m["param_dtype"]).itemsize)


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    z = _z(m)
    attn = z["d"] * (z["H"] + 2 * z["KV"]) * z["hd"] + z["H"] * z["hd"] * z["d"]
    return attn + 2 * z["d"] * z["ff"]


def token_flops(m: Dict, context: int, head: bool) -> int:
    """One token through every layer, attending to ``context`` positions
    (itself included), plus the head when its logits are used."""
    z = _z(m)
    per_layer = 2 * layer_matmul_params(m) + 4 * z["H"] * z["hd"] * context
    return z["L"] * per_layer + (2 * z["d"] * z["V"] if head else 0)


def prefill_flops(m: Dict, length: int, start: int, stop: int) -> int:
    """Positions [start, stop) of a prompt of ``length`` real tokens
    (padding past ``length`` is not needed); the head at its last
    position only."""
    hi = min(stop, length)
    n = max(0, hi - start)
    if n == 0:
        return 0
    z = _z(m)
    # sum over positions p of (p + 1) attended keys
    ctx = (start + 1 + hi) * n // 2
    flops = n * z["L"] * 2 * layer_matmul_params(m)
    flops += z["L"] * 4 * z["H"] * z["hd"] * ctx
    if start <= length - 1 < hi:
        flops += 2 * z["d"] * z["V"]
    return flops


def weight_bytes(m: Dict) -> int:
    """Weights a decode step must read: every layer and the head (the
    embedding is a gather of one row per token, left out)."""
    z = _z(m)
    norms = (2 * z["L"] + 1) * z["d"]
    biases = z["L"] * (z["ff"] + z["d"])
    return (z["L"] * layer_matmul_params(m) + z["V"] * z["d"] + norms
            + biases) * z["bytes"]


def kv_bytes_per_position(m: Dict) -> int:
    z = _z(m)
    return z["L"] * 2 * z["KV"] * z["hd"] * z["bytes"]


def decode_tick(m: Dict, lanes: Iterable[Tuple[int, int]]) -> Dict[str, int]:
    """Needed operations and least bytes of one decode tick.  ``lanes`` holds
    (cached positions at the tick's start, steps the lane was live) for each
    lane; step j of a lane attends to cached + j + 1 positions.  Weights are
    read once for each step in which some lane is live."""
    flops = kv = steps = 0
    for cached, live in lanes:
        steps = max(steps, live)
        for j in range(live):
            flops += token_flops(m, cached + j + 1, head=True)
        # K/V read at each live step: positions already cached, plus the
        # ones this tick wrote before it
        kv += live * cached + live * (live + 1) // 2
    return {"flops": flops,
            "bytes": steps * weight_bytes(m) + kv * kv_bytes_per_position(m),
            "steps": steps}


__all__ = ["layer_matmul_params", "token_flops", "prefill_flops",
           "weight_bytes", "kv_bytes_per_position", "decode_tick"]
