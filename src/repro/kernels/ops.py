"""Jit'd public wrappers around the Pallas kernels.

The kernels resolve ``interpret`` themselves (``kernels.resolve_interpret``):
compiled on a TPU, interpreted elsewhere for correctness validation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash_attention
from .flash_decode import flash_decode as _flash_decode
from .merge_sort import argsort as _argsort


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k)


@partial(jax.jit, static_argnames=("block_k",))
def flash_decode(q, k_cache, v_cache, lengths, *, block_k: int = 512):
    return _flash_decode(q, k_cache, v_cache, lengths, block_k=block_k)


@partial(jax.jit, static_argnames=("num_key_bits", "tile"))
def stable_argsort(keys, *, num_key_bits: int = 12, tile: int = 1024):
    return _argsort(keys, num_key_bits=num_key_bits, tile=tile)


__all__ = ["flash_attention", "flash_decode", "stable_argsort"]
