"""Single-launch monoid scans with a cross-block carry — the shared machinery.

A scan of ``n`` elements on a launch-per-node tree costs ``log n`` kernel
launches; on TPU the grid of one ``pallas_call`` already executes
*sequentially*, so a carry held in VMEM scratch turns the whole scan into
ONE launch: each grid step folds its block into the incoming carry, writes
the block's prefixes, and leaves the fold in the carry for the next step.
This is the "tile-local scan + cross-tile carry" pattern the multi-tile
radix sort uses to turn the ``(num_tiles, R)`` digit-histogram matrix into
global base offsets (``radix_sort.py``), and the machinery of the chunked
SSM scans (``ssm_scan.py``) — hence the generic ``combine``/``unit``
monoid interface rather than a hard-coded sum.

One kernel serves every layout.  Leaves are ``(G, L, R, C)``: ``G``
independent scans over axis 1 whose elements are pytrees of ``(R, C)``
matrices.  Within a block the carry folds in one element at a time (a
``fori_loop`` over the block's leading axis), so every load and store
indexes an untiled leading axis and no slice is ever empty or misaligned —
the body lowers for the TPU as it interprets.  (An in-kernel
``lax.associative_scan`` does not: its odd/even split makes zero-length
vectors, which Mosaic refuses.)

Restrictions: ``combine`` must be associative with identity ``unit`` (the
scan is a left fold of carries, so commutativity is NOT required), and an
element of the carry has the same dtype/shape as one element of the input.

Three layers share the kernel:

* ``tree_scan``    — leaves ``(G, L, R_i, C_i)``, matrix monoids welcome;
  ``rblock`` tiles the rows of the large leaves when the combine acts on
  those rows independently (the mLSTM carry: per-head scalars rescale a
  ``(dh, dh)`` memory row by row).
* ``batched_scan`` — leaves ``(B, L, *feat)`` of one shape under an
  elementwise combine; features are flattened onto 128 lanes and tiled by
  ``fblock`` (the Mamba selective scan).
* ``tile_scan``    — a 1-D array under a scalar monoid (the radix sort's
  histogram offsets).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .launch_trace import record

Combine = Callable[[Any, Any], Any]

LANES = 128        # TPU vector lane width: batched_scan's feature rows


def _scan_kernel(*refs, nleaves, treedef, combine, inclusive, block):
    """One grid step ``(g, f, b)``: fold block ``b`` of scan ``g`` (row tile
    ``f``) into the carry.  ``refs`` is ``x + carry0 + out + carry`` in leaf
    order; the carry scratch persists across the sequential ``b`` axis and
    holds the fold of every earlier block."""
    n = nleaves
    x_refs, c0_refs = refs[:n], refs[n:2 * n]
    o_refs, carry_refs = refs[2 * n:3 * n], refs[3 * n:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        # entering a fresh (scan, row-tile): seed from carry0
        for cr, c0 in zip(carry_refs, c0_refs):
            cr[...] = c0[0, 0]

    def fold(i, _):
        carry = treedef.unflatten([cr[...] for cr in carry_refs])
        new = combine(carry, treedef.unflatten([x[0, i] for x in x_refs]))
        out = new if inclusive else carry
        for o, leaf in zip(o_refs, jax.tree.leaves(out)):
            o[0, i] = leaf.astype(o.dtype)
        for cr, leaf in zip(carry_refs, jax.tree.leaves(new)):
            cr[...] = leaf.astype(cr.dtype)
        return 0

    jax.lax.fori_loop(0, block, fold, 0)


def _check_structure(tree, treedef, what: str) -> list:
    leaves, tdef = jax.tree.flatten(tree)
    if tdef != treedef:
        raise ValueError(f"{what} structure {tdef} != elements {treedef}")
    return leaves


def tree_scan(xs: Any, *, combine: Combine, units: Any,
              carry0: Optional[Any] = None, inclusive: bool = True,
              block: int = 128, rblock: Optional[int] = None,
              interpret: Optional[bool] = None,
              kind: str = "tree_scan") -> Any:
    """Monoid scan over axis 1 of a pytree of ``(G, L, R_i, C_i)`` arrays in
    ONE launch: ``G`` independent scans whose elements are pytrees of
    ``(R_i, C_i)`` matrices — the leaf shapes ``combine`` sees.

    ``units`` is a pytree of scalars (the identity element); ``carry0``
    optionally seeds each scan with a pytree of ``(G, R_i, C_i)`` leaves, so
    the inclusive output is ``carry0 ∘ e_0 ∘ … ∘ e_t`` and the exclusive
    output at t is the state *entering* element t.

    ``rblock`` splits every leaf with more than ``rblock`` rows into
    ``rblock``-row tiles on a grid axis of its own (those leaves must agree
    on the tile count); smaller leaves are loaded whole by every tile.  That
    is exact only when ``combine`` acts on the rows of the tiled leaves
    independently, and it is what keeps a large matrix carry inside VMEM.
    """
    interpret = resolve_interpret(interpret)
    leaves, treedef = jax.tree.flatten(xs)
    u_leaves = _check_structure(units, treedef, "units")
    G, L = leaves[0].shape[:2]
    block = max(1, min(block, L))
    L_pad = -(-L // block) * block

    rbs = []
    for l in leaves:
        if l.ndim != 4 or l.shape[:2] != (G, L):
            raise ValueError(f"tree_scan leaves must be (G={G}, L={L}, R, C),"
                             f" got {l.shape}")
        R = l.shape[2]
        rb = R if rblock is None or R <= rblock else rblock
        if R % rb:
            raise ValueError(f"rblock {rb} must divide the {R} rows of a "
                             f"leaf shaped {l.shape}")
        rbs.append(rb)
    tiles = {l.shape[2] // rb for l, rb in zip(leaves, rbs)} - {1}
    if len(tiles) > 1:
        raise ValueError(f"row-tiled leaves disagree on the tile count: "
                         f"{sorted(tiles)}")
    nf = tiles.pop() if tiles else 1

    if L_pad != L:   # identity padding: the tail only affects padded rows
        leaves = [jnp.concatenate(
            [l, jnp.full((G, L_pad - L) + l.shape[2:], u, l.dtype)], axis=1)
            for l, u in zip(leaves, u_leaves)]
    if carry0 is None:
        c0_leaves = [jnp.full((G, 1) + l.shape[2:], u, l.dtype)
                     for l, u in zip(leaves, u_leaves)]
    else:
        c0_leaves = [jnp.asarray(c).astype(l.dtype).reshape(
            (G, 1) + l.shape[2:]) for c, l in zip(
                _check_structure(carry0, treedef, "carry0"), leaves)]

    def x_spec(l, rb):
        C = l.shape[3]
        tiled = rb != l.shape[2]
        return pl.BlockSpec((1, block, rb, C), lambda g, f, b: (
            g, b, f if tiled else 0, 0))

    def c0_spec(l, rb):   # one carry row per (scan, row tile)
        C = l.shape[3]
        tiled = rb != l.shape[2]
        return pl.BlockSpec((1, 1, rb, C), lambda g, f, b: (
            g, 0, f if tiled else 0, 0))

    grid = (G, nf, L_pad // block)
    record(kind, grid, [(1, block, rb, l.shape[3])
                        for l, rb in zip(leaves, rbs)])
    kernel = functools.partial(
        _scan_kernel, nleaves=len(leaves), treedef=treedef, combine=combine,
        inclusive=inclusive, block=block)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=([x_spec(l, rb) for l, rb in zip(leaves, rbs)]
                  + [c0_spec(l, rb) for l, rb in zip(leaves, rbs)]),
        out_specs=[x_spec(l, rb) for l, rb in zip(leaves, rbs)],
        out_shape=[jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves],
        scratch_shapes=[pltpu.VMEM((rb, l.shape[3]), l.dtype)
                        for l, rb in zip(leaves, rbs)],
        interpret=interpret,
    )(*leaves, *c0_leaves)
    return treedef.unflatten([o[:, :L] for o in outs])


def batched_scan(xs: Any, *, combine: Combine, units: Any,
                 carry0: Optional[Any] = None, inclusive: bool = True,
                 block: int = 128, fblock: int = 2048,
                 interpret: Optional[bool] = None,
                 kind: str = "tree_scan") -> Any:
    """Elementwise-monoid scan over axis 1 of a pytree of ``(B, L, *feat)``
    arrays (identical shapes) in ONE launch.  Features are flattened onto
    rows of ``LANES`` and tiled by about ``fblock`` — legal exactly because
    an elementwise combine never mixes feature columns — so VMEM holds
    ``(block, fblock)`` tiles regardless of the feature extent.  ``carry0``
    leaves are ``(B, *feat)``."""
    leaves, treedef = jax.tree.flatten(xs)
    u_leaves = _check_structure(units, treedef, "units")
    shape = leaves[0].shape
    if any(l.shape != shape for l in leaves):
        raise ValueError("batched_scan needs identically-shaped leaves; "
                         "use tree_scan for matrix monoids")
    B, L = shape[:2]
    feat = shape[2:]
    F = max(1, math.prod(feat))
    R = -(-F // LANES)
    rb_max = max(8, fblock // LANES // 8 * 8)     # sublane-aligned row tile
    rb = R if R <= rb_max else rb_max
    R_pad = -(-R // rb) * rb
    F_pad = R_pad * LANES

    def rows(l, u, n):   # (B, n, *feat) → (B, n, R_pad, LANES)
        flat = l.reshape(B, n, F)
        if F_pad != F:   # unit-fill is arbitrary here; columns never mix
            flat = jnp.concatenate(
                [flat, jnp.full((B, n, F_pad - F), u, l.dtype)], axis=2)
        return flat.reshape(B, n, R_pad, LANES)

    xs4 = treedef.unflatten([rows(l, u, L) for l, u in zip(leaves, u_leaves)])
    c04 = None
    if carry0 is not None:
        c04 = treedef.unflatten([
            rows(jnp.asarray(c).astype(l.dtype), u, 1)[:, 0]
            for c, u, l in zip(_check_structure(carry0, treedef, "carry0"),
                               u_leaves, leaves)])
    outs = tree_scan(xs4, combine=combine, units=units, carry0=c04,
                     inclusive=inclusive, block=block, rblock=rb,
                     interpret=interpret, kind=kind)
    return treedef.unflatten(
        [o.reshape(B, L, F_pad)[..., :F].reshape(shape)
         for o in jax.tree.leaves(outs)])


def tile_scan(x: jnp.ndarray, *, block: int = 256,
              combine: Optional[Combine] = None, unit=0,
              inclusive: bool = False,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Exclusive (default) or inclusive scan of a 1-D array in ONE launch.

    ``combine``/``unit`` default to ``(+, 0)``.  The grid iterates blocks in
    order with the carry in VMEM scratch, so the launch count is 1
    regardless of ``n`` — the property the multi-tile radix sort (and every
    bench row pinned on launch counts) relies on.
    """
    combine = jnp.add if combine is None else combine
    n = x.shape[0]
    if n == 0:
        return x
    (out,) = tree_scan((x.reshape(1, n, 1, 1),),
                       combine=lambda a, b: (combine(a[0], b[0]),),
                       units=(unit,), inclusive=inclusive, block=block,
                       interpret=interpret, kind="tile_scan")
    return out.reshape(n)


def histogram_offsets(hist: jnp.ndarray, *, block: int = 256,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Global base offsets from a ``(num_tiles, R)`` digit-histogram matrix.

    ``offsets[t, d]`` = #(elements with digit < d anywhere) + #(elements
    with digit d in tiles before ``t``) — the destination of tile ``t``'s
    first digit-``d`` element in a stable multi-tile counting pass.  That
    is exactly the exclusive scan of the histogram flattened digit-major
    (transpose → scan → transpose back), one ``tile_scan`` launch.
    """
    nt, r = hist.shape
    flat = hist.T.reshape(nt * r)
    scanned = tile_scan(flat, block=block, interpret=interpret)
    return scanned.reshape(r, nt).T


__all__ = ["tile_scan", "tree_scan", "batched_scan", "histogram_offsets",
           "LANES"]
