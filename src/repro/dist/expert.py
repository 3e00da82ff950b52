"""Expert-parallel MoE dispatch over ``shard_map`` — the paper's stable
sort as the distribution mechanism.

``moe_shard_map`` is the sort-based (dropless) MoE layer of
``repro.models.moe`` pushed onto a mesh: tokens are stably sorted by expert
id (§3.7 — intra-expert token order is preserved, so the combine stays a
cheap scatter-add), the token rows shard over ``data``, and the expert bank
shards over ``model``.  Each device computes the contribution of *its*
experts to every routed row via a one-hot segment mask (out-of-range ids
one-hot to zero rows, so masking is free) and a single ``psum`` over the
expert axis folds the partials — no all-to-all materialization of
per-expert buffers, no capacity drops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ModelConfig
from ..models.layers import Params
from ..models.moe import sort_combine, sort_route


def moe_shard_map(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                  mesh: Mesh, *, axis: str = "model",
                  token_axis: str = "data", sort_fn=None):
    """Expert-parallel dropless MoE.  x: (B, S, D) → (out, aux_loss).

    Matches ``moe_sort_dispatch`` exactly — the shared ``sort_route`` /
    ``sort_combine`` prelude/epilogue with the expert GEMMs partitioned
    over the expert axis; ``sort_fn`` as in that function (default stable
    argsort, pass the Pallas merge sort to make dispatch literally §3.7).
    """
    E = cfg.num_experts
    n = mesh.shape[axis]
    if E % n:
        raise ValueError(f"'{axis}' size {n} must divide num_experts={E}")
    B, S, _ = x.shape
    xd, sorted_e, sorted_tok, sorted_p, aux = sort_route(params, cfg, x,
                                                         sort_fn)
    rows = B * S * cfg.top_k
    dpn = mesh.shape.get(token_axis, 1)
    tok = token_axis if (token_axis in mesh.shape and rows % dpn == 0) \
        else None
    e_per = E // n

    def spmd(gate_blk, up_blk, down_blk, xd_blk, e_blk):
        idx = jax.lax.axis_index(axis)
        # local expert ids; out-of-range one-hots to an all-zero row
        seg = jax.nn.one_hot(e_blk - idx * e_per, e_per, dtype=xd_blk.dtype)
        h = jnp.einsum("td,edf,te->tf", xd_blk, gate_blk, seg)
        u = jnp.einsum("td,edf,te->tf", xd_blk, up_blk, seg)
        y = jnp.einsum("tf,efd,te->td", jax.nn.silu(h) * u, down_blk, seg)
        return jax.lax.psum(y, axis)

    y = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(tok, None), P(tok)),
        out_specs=P(tok, None), check_vma=False)(
        params["gate"], params["up"], params["down"], xd, sorted_e)

    return sort_combine(params, cfg, x, y, sorted_tok, sorted_p), aux


__all__ = ["moe_shard_map"]
