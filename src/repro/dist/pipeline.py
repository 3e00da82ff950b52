"""Pipeline-parallel microbatch schedules, driven by ``core.plan``.

The tick order of a pipeline is a *scheduling policy decision*, so it comes
from the same machinery as every other schedule in this repo: a microbatch
order is the leaf order of a ``build_plan(bound_depth(WorkRange(0, n)))``
division tree — the static join-scheduler divide phase — not an ad-hoc
``range(n)``.  ``schedule_ticks`` turns that order into the classic
fill–drain tick table (for forward-only execution the 1F1B and GPipe
schedules coincide: every tick is a forward micro-step), ``bubble_fraction``
is its analytic idle share, and ``pipeline_forward`` executes the table over
a real device mesh with ``shard_map`` + ``ppermute``.
"""

from __future__ import annotations

import math
from typing import Callable, List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core import WorkRange, bound_depth, build_plan


def microbatch_order(num_microbatches: int) -> List[int]:
    """Microbatch injection order = leaf order of a Kvik division tree.

    ``bound_depth`` to ``ceil(log2 n)`` divides the microbatch range into
    singletons; the plan's left-to-right leaf traversal is the order the
    join scheduler would execute them in.
    """
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches={num_microbatches} must be >= 1")
    n = num_microbatches
    depth = math.ceil(math.log2(n)) if n > 1 else 0
    plan = build_plan(bound_depth(WorkRange(0, n), depth))
    return [i for w in plan.leaves() for i in range(w.start, w.stop)]


def schedule_ticks(stages: int, num_microbatches: int) -> List[List[str]]:
    """Fill–drain tick table: ``table[t][s]`` is the microbatch id stage
    ``s`` processes at tick ``t`` (``"-"`` = bubble).  ``num_microbatches +
    stages - 1`` ticks; stage ``s`` starts at tick ``s``."""
    if stages < 1:
        raise ValueError(f"stages={stages} must be >= 1")
    order = microbatch_order(num_microbatches)
    n = len(order)
    table = []
    for t in range(n + stages - 1):
        row = []
        for s in range(stages):
            i = t - s
            row.append(str(order[i]) if 0 <= i < n else "-")
        table.append(row)
    return table


def bubble_fraction(stages: int, num_microbatches: int) -> float:
    """Idle share of the fill–drain schedule: ``(p-1) / (n + p - 1)``.

    Matches a brute-force count of ``"-"`` cells in ``schedule_ticks``
    (property-pinned in tests/test_dist_properties.py); driving microbatch
    count up is the only lever that amortizes the fixed fill+drain cost.
    """
    if stages < 1:
        raise ValueError(f"stages={stages} must be >= 1")
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches={num_microbatches} must be >= 1")
    return (stages - 1) / (num_microbatches + stages - 1)


def pipeline_forward(stage_fn: Callable, ws, xs, mesh: Mesh, *,
                     axis: str = "pipe"):
    """Run ``xs`` through ``stages`` pipeline stages laid out on ``axis``.

    ``stage_fn(x_mb, w) -> y_mb`` is one stage; ``ws`` stacks per-stage
    weights on axis 0 (sharded one-per-device over ``axis``); ``xs`` has
    shape ``(num_microbatches, mb_batch, ...)``.  Each tick every device
    runs one forward micro-step and hands its activation to the right
    neighbor via ``ppermute`` — the tick sequence is exactly
    ``schedule_ticks``'s table, whose microbatch order came from the plan.
    Returns outputs in the original microbatch order, replicated.
    """
    stages = mesh.shape[axis]
    n_mb = xs.shape[0]
    if ws.shape[0] != stages:
        raise ValueError(f"ws carries {ws.shape[0]} stages for a "
                         f"{stages}-wide '{axis}' mesh axis")
    order = microbatch_order(n_mb)
    shift = [(i, i + 1) for i in range(stages - 1)]

    def spmd(w_blk, xs_all):
        idx = jax.lax.axis_index(axis)
        w = w_blk[0]
        state = jnp.zeros_like(xs_all[0])
        outs = jnp.zeros_like(xs_all)
        for t in range(n_mb + stages - 1):
            # receive last tick's activation from the left neighbor
            recv = jax.lax.ppermute(state, axis, perm=shift) \
                if stages > 1 else state
            feed = order[t] if t < n_mb else order[-1]
            inp = jnp.where(idx == 0, xs_all[feed], recv)
            out = stage_fn(inp, w)
            emit = t - (stages - 1)
            if 0 <= emit < n_mb:     # drain window of the last stage
                outs = jnp.where(idx == stages - 1,
                                 outs.at[order[emit]].set(out), outs)
            state = out
        # replicate the last stage's buffer so out_specs can be unsharded
        return jax.lax.psum(
            jnp.where(idx == stages - 1, outs, jnp.zeros_like(outs)), axis)

    nd = xs.ndim
    return jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis, *([None] * (ws.ndim - 1))), P(*([None] * nd))),
        out_specs=P(*([None] * nd)), check_vma=False)(ws, xs)


__all__ = ["microbatch_order", "schedule_ticks", "bubble_fraction",
           "pipeline_forward"]
