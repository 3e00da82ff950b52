"""Latency-hiding collective matmuls via ``shard_map``.

The two decompositions every tensor-parallel transformer layer reduces to
(cf. "Overlap communication with computation", Wang et al.'s collective
matmul — and on our side: each is a reduction tree over per-shard tasks,
i.e. a Kvik plan executed by GSPMD):

* ``allgather_matmul`` — column-parallel projection.  Activations arrive
  row-sharded; instead of one blocking all-gather followed by the matmul,
  each device multiplies the row block it currently holds and ring-shifts
  (``ppermute``) the block, overlapping transfer with compute.
* ``matmul_reducescatter`` — row-parallel projection.  Each device holds a
  contraction slice, computes a full-size partial product, and the partials
  ring-accumulate so every step's transfer overlaps the next chunk's add;
  rows end up scattered over the axis.

Both return the mathematically exact ``x @ w`` (pinned in tests/test_dist).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def allgather_matmul(x: jnp.ndarray, w: jnp.ndarray, mesh: Mesh, *,
                     axis: str = "model") -> jnp.ndarray:
    """``x @ w`` with x row-sharded and w column-sharded over ``axis``.

    Per device: n_axis steps of (local block matmul, ring-shift block) —
    the all-gather is decomposed into the steps so compute hides it.
    """
    n = mesh.shape[axis]
    M, K = x.shape
    N = w.shape[1]
    if M % n or N % n:
        raise ValueError(f"allgather_matmul: axis '{axis}' size {n} must "
                         f"divide M={M} and N={N}")
    ring = [(i, (i + 1) % n) for i in range(n)]

    def spmd(x_blk, w_blk):
        idx = jax.lax.axis_index(axis)
        mb = x_blk.shape[0]
        y = jnp.zeros((M, w_blk.shape[1]), x_blk.dtype)
        blk = x_blk
        for step in range(n):
            src = (idx - step) % n       # original owner of `blk`
            y = jax.lax.dynamic_update_slice(y, blk @ w_blk, (src * mb, 0))
            if step < n - 1:
                blk = jax.lax.ppermute(blk, axis, perm=ring)
        return y

    return jax.shard_map(spmd, mesh=mesh,
                         in_specs=(P(axis, None), P(None, axis)),
                         out_specs=P(None, axis), check_vma=False)(x, w)


def matmul_reducescatter(x: jnp.ndarray, w: jnp.ndarray, mesh: Mesh, *,
                         axis: str = "model") -> jnp.ndarray:
    """``x @ w`` with the contraction dim K sharded over ``axis``.

    Each device computes its K-slice partial, then the partials
    ring-accumulate row-chunk by row-chunk (a hand-rolled reduce-scatter:
    every step's ``ppermute`` overlaps the next local add), leaving device
    ``d`` with the finished rows ``[d·M/n, (d+1)·M/n)``.
    """
    n = mesh.shape[axis]
    M, K = x.shape
    if M % n or K % n:
        raise ValueError(f"matmul_reducescatter: axis '{axis}' size {n} "
                         f"must divide M={M} and K={K}")
    mb = M // n
    ring = [(i, (i + 1) % n) for i in range(n)]

    def spmd(x_blk, w_blk):
        idx = jax.lax.axis_index(axis)
        partial = x_blk @ w_blk                      # (M, N) partial sums
        if n == 1:
            return partial

        def chunk(d):                                # rows destined for d
            return jax.lax.dynamic_slice_in_dim(partial, d * mb, mb, 0)

        # ring reduce-scatter: the packet destined for row-chunk c starts at
        # device c+1 and travels forward; device d adds chunk (d-k-1) at hop
        # k, so after n-1 hops it holds its own chunk, fully reduced.
        acc = chunk((idx - 1) % n)
        for k in range(1, n):
            acc = jax.lax.ppermute(acc, axis, perm=ring) \
                + chunk((idx - k - 1) % n)
        return acc

    return jax.shard_map(spmd, mesh=mesh,
                         in_specs=(P(None, axis), P(axis, None)),
                         out_specs=P(axis, None), check_vma=False)(x, w)


__all__ = ["allgather_matmul", "matmul_reducescatter"]
