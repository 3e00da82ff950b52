"""Mixture-of-Experts: routing, dispatch, expert FFNs, shared experts.

Two dispatch strategies, both first-class:

* ``einsum`` (baseline / paper-faithful phase): GShard-style grouped one-hot
  dispatch.  Tokens are viewed in groups; a (G, S, E, C) dispatch tensor is
  contracted against activations.  Under GSPMD (tokens sharded over ``data``,
  experts over ``model``) the contraction lowers to all-to-alls.  Its FLOP
  overhead is *measured* in §Roofline and becomes a hillclimb target.

* ``sort`` (the Kvik showcase): tokens are stably sorted by expert id — the
  paper's parallel stable merge sort, §3.7 — then gathered into capacity bins.
  Stability preserves intra-expert token order, which keeps the combine a
  cheap gather.  On TPU the sort is the Pallas ``merge_sort`` kernel; the
  jnp path uses ``jnp.argsort(..., stable=True)``.  Used inside ``shard_map``
  expert-parallel dispatch (``repro.dist.moe_shard_map``) and in examples.

Router: softmax → top-k → renormalize (DeepSeek convention); auxiliary
load-balance loss returned for the trainer.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Params, dense_init, swiglu, swiglu_init


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    dt = cfg.pdtype()
    params: Params = {
        "router": dense_init(ks[0], d, e, dt),
        "gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                 / math.sqrt(d)).astype(dt),
        "up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
               / math.sqrt(d)).astype(dt),
        "down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                 / math.sqrt(f)).astype(dt),
    }
    if cfg.num_shared_experts > 0:
        params["shared"] = swiglu_init(
            ks[4], d, f * cfg.num_shared_experts, dt)
    return params


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route_topk(router_w: jnp.ndarray, x: jnp.ndarray, top_k: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (..., D) → (probs (..., k), experts (..., k) int32, aux_loss scalar).

    Softmax over experts, top-k, renormalized.  The aux loss is the standard
    Switch/GShard load-balance term: E · Σ_e f_e · p_e.
    """
    logits = jnp.einsum("...d,de->...e", x, router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    e = router_w.shape[-1]
    # fraction of tokens routed to each expert (first choice) & mean prob
    first = jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32)
    f_e = first.reshape(-1, e).mean(0)
    p_e = probs.reshape(-1, e).mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return top_p.astype(x.dtype), top_e.astype(jnp.int32), aux


def capacity_per_group(group_size: int, num_experts: int, top_k: int,
                       capacity_factor: float) -> int:
    c = math.ceil(group_size * top_k * capacity_factor / num_experts)
    return max(4, ((c + 3) // 4) * 4)


# ---------------------------------------------------------------------------
# einsum (GShard) dispatch
# ---------------------------------------------------------------------------

def moe_einsum(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
               group_size: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) → (out (B,S,D), aux_loss).

    Tokens are regrouped to (G, group_size, D); G stays divisible by the data
    axis because B is.  Capacity overflows drop (standard GShard semantics —
    the residual connection carries dropped tokens).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    g = min(group_size, S)
    G = T // g
    xg = x.reshape(G, g, D)

    probs, experts, aux = route_topk(params["router"], xg, K)  # (G,g,K)
    C = capacity_per_group(g, E, K, cfg.capacity_factor)

    dispatch = jnp.zeros((G, g, E, C), x.dtype)
    combine = jnp.zeros((G, g, E, C), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)
    for j in range(K):
        onehot = jax.nn.one_hot(experts[..., j], E, dtype=jnp.int32)  # (G,g,E)
        pos = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]
        counts = counts + onehot.sum(axis=1)
        keep = (pos < C) & (onehot > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=x.dtype)
        sel = (keep.astype(x.dtype))[..., None] * pos_oh           # (G,g,E,C)
        sel = sel * onehot.astype(x.dtype)[..., None]
        dispatch = dispatch + sel
        combine = combine + sel.astype(jnp.float32) * \
            probs[..., j].astype(jnp.float32)[..., None, None]

    from ..dist.sharding import constrain, dp
    from jax.sharding import PartitionSpec as P
    # Two expert-parallel regimes (EXPERIMENTS.md §Perf, hillclimb A):
    # * moe_2d_shard (Jamba-398B): stationary weights, 2-D sharded
    #   (experts × model, hidden × data); token groups replicate over 'data'
    #   and a psum folds the f-sharded partials.  No weight all-gathers, so
    #   XLA cannot hoist 796 GB of experts out of the layer scan (the
    #   failure mode that produced 84 GiB/device temps).
    # * EP-only (small expert banks): tokens stay 'data'-sharded, experts
    #   over 'model' — the classic all-to-all MoE; no per-layer psum.
    g_ax = None if cfg.moe_2d_shard else dp()
    f_ax = dp() if cfg.moe_2d_shard else None   # pod×data when multi-pod
    xe = jnp.einsum("gsd,gsec->egcd", xg, dispatch)                # (E,G,C,D)
    xe = constrain(xe, P("model", g_ax, None, None))
    h = jnp.einsum("egcd,edf->egcf", xe, params["gate"])
    u = jnp.einsum("egcd,edf->egcf", xe, params["up"])
    h = jax.nn.silu(h) * u
    h = constrain(h, P("model", g_ax, None, f_ax))
    ye = jnp.einsum("egcf,efd->egcd", h, params["down"])
    ye = constrain(ye, P("model", g_ax, None, None))
    out = jnp.einsum("egcd,gsec->gsd", ye, combine.astype(x.dtype))
    out = out.reshape(B, S, D)

    if cfg.num_shared_experts > 0:
        out = out + swiglu(params["shared"], x)
    return out, aux


# ---------------------------------------------------------------------------
# sort-based dispatch (the paper's stable sort at work)
# ---------------------------------------------------------------------------

def sort_route(params: Params, cfg: ModelConfig, x: jnp.ndarray,
               sort_fn=None):
    """Shared sort-dispatch prelude: route, flatten to (T·K,) assignments,
    stably sort by expert id (§3.7 — stability keeps the combine a gather).

    Returns ``(xd, sorted_e, sorted_tok, sorted_p, aux)`` with ``xd`` the
    permuted activations (T·K, D).  ``sort_fn(keys) -> order`` must be a
    *stable* argsort — default ``jnp.argsort(stable=True)``; the string
    ``"pallas"`` routes through the one-launch dispatch kernel
    (``kernels.radix_sort.moe_dispatch_sort``): the stable sort by expert
    id is a single ``pallas_call`` at any T, and the activation rows follow
    by one gather (``jit=True`` caches the compiled kernel per shape).
    Expert counts beyond the kernel's 256-expert digit width fall back to
    the multi-tile radix ``argsort`` + gather.  Used by
    ``moe_sort_dispatch`` and ``repro.dist.expert.moe_shard_map``.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, D)
    probs, experts, aux = route_topk(params["router"], xf, K)     # (T,K)
    if sort_fn == "pallas":
        if E <= 256:
            from ..kernels.radix_sort import moe_dispatch_sort
            xd, sorted_e, sorted_tok, sorted_p = moe_dispatch_sort(
                xf, experts, probs, num_experts=E, jit=True)
            return xd, sorted_e, sorted_tok, sorted_p, aux
        from ..kernels.merge_sort import argsort as kernel_argsort
        bits = max(1, math.ceil(math.log2(max(2, E))))
        sort_fn = functools.partial(kernel_argsort, num_key_bits=bits,
                                    jit=True)

    flat_e = experts.reshape(T * K)
    flat_p = probs.reshape(T * K)
    token_of = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)

    order = (sort_fn(flat_e) if sort_fn is not None
             else jnp.argsort(flat_e, stable=True))
    sorted_e = flat_e[order].astype(jnp.int32)
    sorted_tok = token_of[order]
    sorted_p = flat_p[order]
    return xf[sorted_tok], sorted_e, sorted_tok, sorted_p, aux


def sort_combine(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 y: jnp.ndarray, sorted_tok: jnp.ndarray,
                 sorted_p: jnp.ndarray) -> jnp.ndarray:
    """Shared epilogue: combine-weight scale, scatter-add back to token
    order, shared-expert residual."""
    B, S, D = x.shape
    y = y * sorted_p[:, None].astype(y.dtype)
    out = jnp.zeros((B * S, D), y.dtype).at[sorted_tok].add(y)
    out = out.reshape(B, S, D).astype(x.dtype)
    if cfg.num_shared_experts > 0:
        out = out + swiglu(params["shared"], x)
    return out


def moe_sort_dispatch(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
                      sort_fn=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-shard sort-based MoE (exact, gather/scatter based).

    Capacity-free (dropless): every token is processed; expert batches are
    ragged, realized as one grouped einsum over a (T·K, D) permuted
    activation with segment boundaries.  See ``sort_route`` for the sort.
    """
    E = cfg.num_experts
    xd, sorted_e, sorted_tok, sorted_p, aux = sort_route(params, cfg, x,
                                                         sort_fn)
    # ragged expert GEMMs via one-hot masked einsum over experts — on TPU this
    # is a ragged/grouped matmul; here the jnp fallback keeps shapes static.
    seg = jax.nn.one_hot(sorted_e, E, dtype=x.dtype)              # (T·K, E)
    h = jnp.einsum("td,edf,te->tf", xd, params["gate"], seg)
    u = jnp.einsum("td,edf,te->tf", xd, params["up"], seg)
    y = jnp.einsum("tf,efd,te->td", jax.nn.silu(h) * u, params["down"], seg)
    return sort_combine(params, cfg, x, y, sorted_tok, sorted_p), aux


def moe_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
              strategy: str = "einsum", group_size: int = 256,
              sort_fn=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if strategy == "einsum":
        return moe_einsum(params, cfg, x, group_size=group_size)
    if strategy == "sort":
        return moe_sort_dispatch(params, cfg, x, sort_fn=sort_fn)
    raise ValueError(f"unknown MoE strategy {strategy!r}")


__all__ = ["moe_init", "route_topk", "capacity_per_group", "moe_einsum",
           "sort_route", "sort_combine", "moe_sort_dispatch", "moe_apply"]
