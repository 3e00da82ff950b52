"""Pallas flash-decode: one-token attention with KV-range splitting.

This kernel is the paper's divide-and-conquer (wrap_iter) pattern on silicon:
a Kvik policy splits the KV range [0, S) into blocks (``demand_split`` — the
adaptive schedule: exactly as many blocks as there is parallelism demand);
each grid step computes a *partial* softmax (m, l, acc) over its block; the
partials are then fused by the plan's symmetric **reduction tree**
(``combine_partials`` — associative, so the tree shape is free to match the
hardware, exactly the paper's argument for delegating reduction placement).

GQA: q-heads grouped per kv-head in the index map, like flash_attention.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import SeqWork, demand_split
from . import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, bk: int):
    """Grid (B, H, nk).  Partials per kv block.

    q_ref: (1,1,hd); k_ref/v_ref: (1,bk,1,hd); len_ref: (1,) valid length.
    Outputs m/l: (1,1,1); acc: (1,1,1,hd).
    """
    ik = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale              # (hd,)
    k = k_ref[0, :, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)
    valid = len_ref[0]
    pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    s = jnp.einsum("kd,d->k", k, q)
    s = jnp.where(pos < valid, s, NEG_INF)
    m = s.max()
    p = jnp.exp(s - m)
    l = p.sum()
    acc = jnp.einsum("k,kd->d", p, v)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l
    acc_ref[0, 0, 0] = acc


def decode_partials(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                    block_k: int = 512, scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """q: (B,H,hd); caches: (B,S,KV,hd); lengths: (B,).
    Returns per-block partials (m, l, acc) with leading nk axis."""
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    bk = min(block_k, S)
    assert S % bk == 0
    nk = S // bk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk)
    m, l, acc = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, ik: (b, h, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, ik, G=G: (b, ik, h // G, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, ik, G=G: (b, ik, h // G, 0)),
            pl.BlockSpec((1,), lambda b, h, ik: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, h, ik: (b, h, ik)),
            pl.BlockSpec((1, 1, 1), lambda b, h, ik: (b, h, ik)),
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nk), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nk), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nk, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_cache, v_cache, lengths)
    return m, l, acc


def combine_partials(part_a, part_b):
    """Associative LSE-combine of two softmax partials — one node of the
    Kvik reduction tree."""
    m1, l1, a1 = part_a
    m2, l2, a2 = part_b
    m = jnp.maximum(m1, m2)
    s1 = jnp.exp(m1 - m)
    s2 = jnp.exp(m2 - m)
    return (m, l1 * s1 + l2 * s2,
            a1 * s1[..., None] + a2 * s2[..., None])


def flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                 lengths: jnp.ndarray, *, block_k: int = 512,
                 scale: Optional[float] = None, demand: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Full decode attention: Pallas partials + plan-driven reduction tree.

    ``demand`` (default: #kv-blocks) sets the adaptive-schedule parallelism:
    the KV range is demand_split into that many pieces, and the partials are
    reduced pairwise along the plan tree.
    """
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    S = k_cache.shape[1]
    bk = min(block_k, S)
    nk = S // bk
    m, l, acc = decode_partials(q, k_cache, v_cache, lengths,
                                block_k=bk, scale=scale, interpret=interpret)

    plan = demand_split(SeqWork(0, nk), demand or nk)

    def leaf(work):
        sl = slice(work.start, work.stop)
        parts = [(m[:, :, i], l[:, :, i], acc[:, :, i])
                 for i in range(work.start, work.stop)]
        out = parts[0]
        for p in parts[1:]:
            out = combine_partials(out, p)
        return out

    mF, lF, aF = plan.map_reduce(leaf, combine_partials)
    return (aF / jnp.maximum(lF, 1e-30)[..., None]).astype(q.dtype)


__all__ = ["flash_decode", "decode_partials", "combine_partials"]
