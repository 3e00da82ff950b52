"""Pallas TPU flash attention (GQA, causal) — the train/prefill hot spot.

Schedule = the Kvik tile plan from ``repro.models.attention.attn_chunk_sizes``
realized on hardware: grid (batch, q-heads, q-blocks, kv-blocks); the kv-block
axis is the innermost (sequential on TPU) so the running-softmax state lives
in VMEM scratch across kv steps.  BlockSpecs stage (bq, hd) / (bk, hd) tiles
HBM→VMEM; MXU dims (bq, bk, hd) are multiples of 128 by construction.

GQA is handled in the index map: the kv-head for q-head h is ``h // G`` — no
repeated-KV materialization, matching the jnp reference.

Validated in interpret mode against ``ref.attention_reference`` over shape ×
dtype sweeps (tests/test_kernels.py).  On real TPUs the causal upper-triangle
blocks would be pruned from the grid (q-dependent kv extent); in this
container the mask branch keeps correctness (the compiled dry-run uses the
jnp blockwise path, which does prune — see DESIGN.md).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                # (bk, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (bq, bk)
    if causal:
        iq = pl.program_id(2)
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + \
        jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Sq, H, hd)  k,v: (B, Sk, KV, hd) → (B, Sq, H, hd)."""
    interpret = resolve_interpret(interpret)
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, "seq must tile evenly"
    nq, nk = Sq // bq, Sk // bk

    qt = q.transpose(0, 2, 1, 3)   # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)   # (B, KV, Sk, hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


__all__ = ["flash_attention"]
