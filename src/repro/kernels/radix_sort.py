"""In-kernel LSD radix tile sort — the merge sort's tile phase, rebuilt.

The seed tile sort ran an O(m·log²m) bitonic network per tile: at
``tile=1024`` that is 55 compare-exchange stages — ~550 traced ops per
kernel body, and trace/compile/dispatch overhead proportional to that is
exactly the per-task overhead that erases task-parallel speedups
("Runtime vs Scheduler", PAPERS.md).  This module replaces it with a
stable LSD radix sort whose whole pass loop is a single in-kernel
``fori_loop``: ``ceil(sort_bits / r)`` data-parallel passes, each a
constant ~20 traced ops, no 1-D gathers anywhere.

One pass (``r``-bit digit, radix ``R = 2^r``):

1. **Rank by masked cumulative sum.**  ``onehot[i, d] = [digit_i == d]``
   (a broadcast compare against a 2-D iota — no gather); an inclusive
   cumsum down the tile axis counts, for every element, how many earlier
   elements share its digit; the digit histogram's exclusive scan adds the
   count of all smaller digits.  ``rank = Σ_d onehot·(incl + excl) − 1``
   selects both terms in one masked reduction.  Stable by construction:
   equal digits keep their relative order.

2. **Gather-free placement.**  ``rank`` is a bijection onto ``[0, m)``, so
   scatter-by-rank is a permutation-matrix product.  A full ``(m, m)``
   one-hot is memory-hostile; instead ``rank`` splits as ``(row, col) =
   (rank // C, rank % C)`` and the move becomes one small matmul per
   payload: ``out[row, col] = Σ_i v_i · rowoh[i, row] · coloh[i, col]``
   (an MXU-shaped ``(rows, m) × (m, C)`` contraction).  Every output cell
   receives exactly one element, so f32 accumulation is exact for
   payloads below 2^24; wider payloads move as two 16-bit halves.

Fused pack (`radix_tile_sort_packed`): the kernel takes *raw keys* and
emits sorted ``key << idx_bits | global_index`` words — the pack that used
to be a standalone elementwise launch happens in-kernel.  Fusion also
makes the sort cheaper, not just launch-leaner: in-tile the index bits are
the (already ordered) local positions, so a *stable* rank over the key
digits alone reproduces the packed order exactly — 12-bit keys need
``ceil(12/r)`` passes instead of ``ceil((12+idx_bits)/r)``.  The moved
payload is the compact composite ``key·tile + position`` (≤ 24 bits for
the default ``tile=1024``/``num_key_bits≤14`` — single-einsum placement).

``group`` batches several tiles per grid cell (leading block axis) purely
to amortize interpret-mode per-op overhead; on a real TPU footprint is
``group·tile`` words of payload plus the ``(group·tile, R)`` one-hot, so
keep ``group`` small (default 8 ≈ 2 MB of VMEM at ``tile=1024``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.plan import digit_passes
from . import resolve_interpret
from .launch_trace import record

# the single definition — merge_sort imports it: pad words must compare
# above every real packed key in both the tile and the merge phases
SENTINEL = 0xFFFFFFFF

# int16 rank arithmetic holds counts up to 2·tile; keep a wide margin
_MAX_RADIX_TILE = 1 << 13


def _check_tile(tile: int, digit_bits: int) -> None:
    if tile & (tile - 1):
        raise ValueError(f"radix tile must be a power of two, got {tile}")
    if tile > _MAX_RADIX_TILE:
        raise ValueError(f"radix tile sort supports tile ≤ {_MAX_RADIX_TILE} "
                         f"(int16 rank arithmetic), got {tile}")
    if not 1 <= digit_bits <= 8:
        raise ValueError(f"digit_bits must be in [1, 8], got {digit_bits}")


def _pick_group(num_tiles: int, group: int) -> int:
    return math.gcd(num_tiles, max(1, group))


def _placement_split(m: int):
    """Balanced (rows, cols) factorization of the tile for the rank
    decomposition — rows·cols == m, both powers of two."""
    lb = m.bit_length() - 1
    rows = 1 << (lb // 2)
    return rows, m // rows


def _rank_and_counts(vals: jnp.ndarray, shift, digit_mask, radix: int):
    """Stable rank of each element of each row by the masked digit at
    ``shift`` (``digit_mask`` narrows the final pass so bits beyond the
    sort window never participate — tie order outside it is preserved),
    plus the per-row digit histogram.

    vals: (G, m) uint32 → ((G, m) int16 rank — a per-row permutation —
    and (G, R) int32 counts).  Masked-cumsum formulation: no gathers, one
    (G, m, R) intermediate.
    """
    G, m = vals.shape
    digit = ((vals >> shift) & digit_mask).astype(jnp.int16)
    onehot = (digit[..., None] ==
              jax.lax.broadcasted_iota(jnp.int16, (G, m, radix), 2)
              ).astype(jnp.int16)
    incl = jnp.cumsum(onehot, axis=1)                     # within-digit counts
    counts = incl[:, -1, :].astype(jnp.int32)             # digit histogram
    excl = (jnp.cumsum(counts, axis=1) - counts).astype(jnp.int16)
    # one masked reduction selects own-digit (incl − 1) + smaller-digit total
    rank = jnp.sum(onehot * (incl + excl[:, None, :]), axis=2) - 1
    return rank, counts


def _rank_by_digit(vals: jnp.ndarray, shift, digit_mask,
                   radix: int) -> jnp.ndarray:
    return _rank_and_counts(vals, shift, digit_mask, radix)[0]


def _placement_onehots(rank: jnp.ndarray, rows: int, cols: int):
    G, m = rank.shape
    rowoh = ((rank // cols)[..., None] ==
             jax.lax.broadcasted_iota(jnp.int16, (G, m, rows), 2)
             ).astype(jnp.float32)
    coloh = ((rank % cols)[..., None] ==
             jax.lax.broadcasted_iota(jnp.int16, (G, m, cols), 2)
             ).astype(jnp.float32)
    return rowoh, coloh


def _permute_narrow(v: jnp.ndarray, rowoh, coloh) -> jnp.ndarray:
    """Place values < 2^24 by rank (exact f32, single contraction)."""
    G, m = v.shape
    out = jnp.einsum("gmr,gmc->grc", v.astype(jnp.float32)[..., None] * rowoh,
                     coloh, preferred_element_type=jnp.float32)
    return out.reshape(G, m).astype(jnp.uint32)


def _permute_u32(v: jnp.ndarray, rowoh, coloh) -> jnp.ndarray:
    """Place full uint32 payloads by rank as two exact 16-bit halves."""
    lo = _permute_narrow(v & jnp.uint32(0xFFFF), rowoh, coloh)
    hi = _permute_narrow(v >> 16, rowoh, coloh)
    return (hi << 16) | lo


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _pass_mask(p, digit_bits: int, sort_bits: int):
    """Digit mask of pass ``p``: full ``digit_bits`` except the final pass,
    which narrows to the leftover ``sort_bits`` (the ``DigitPass.bits``
    arithmetic, applied in-kernel so out-of-window bits never rank)."""
    width = jnp.minimum(jnp.uint32(digit_bits),
                        jnp.uint32(sort_bits) -
                        p.astype(jnp.uint32) * digit_bits)
    return (jnp.uint32(1) << width) - jnp.uint32(1)


def _radix_sort_kernel(x_ref, o_ref, *, num_passes, digit_bits, sort_bits,
                       key_shift):
    """Generic per-tile stable LSD sort of packed uint32 words by the bits
    in [key_shift, key_shift + sort_bits)."""
    G, m = x_ref.shape
    rows, cols = _placement_split(m)
    radix = 1 << digit_bits

    def one_pass(p, x):
        shift = jnp.uint32(key_shift) + p.astype(jnp.uint32) * digit_bits
        rank = _rank_by_digit(x, shift, _pass_mask(p, digit_bits, sort_bits),
                              radix)
        rowoh, coloh = _placement_onehots(rank, rows, cols)
        return _permute_u32(x, rowoh, coloh)

    o_ref[...] = jax.lax.fori_loop(0, num_passes, one_pass, x_ref[...])


def _fused_tile_sort_kernel(k_ref, o_ref, *, n, num_key_bits, idx_bits,
                            num_passes, digit_bits, sort_bits, unpack):
    """Fused pack + radix tile sort (+ optional unpack).

    k_ref: (G, tile) int32 raw keys (pad rows carry the max key so they
    sort last).  The in-kernel payload is the composite ``key·tile + pos``;
    global packed words (or, with ``unpack``, the int32 order itself) are
    materialized only at the output write.
    """
    G, m = k_ref.shape
    lb = m.bit_length() - 1
    rows, cols = _placement_split(m)
    radix = 1 << digit_bits
    narrow = lb + num_key_bits <= 24          # composite exact in one einsum

    pos = jax.lax.broadcasted_iota(jnp.uint32, (G, m), 1)
    c0 = (k_ref[...].astype(jnp.uint32) << lb) | pos

    def one_pass(p, c):
        # rank on the *key* digits only: the position bits below lb are
        # already in order, and LSD stability carries them for free
        shift = jnp.uint32(lb) + p.astype(jnp.uint32) * digit_bits
        rank = _rank_by_digit(c, shift,
                              _pass_mask(p, digit_bits, sort_bits), radix)
        rowoh, coloh = _placement_onehots(rank, rows, cols)
        perm = _permute_narrow if narrow else _permute_u32
        return perm(c, rowoh, coloh)

    c = jax.lax.fori_loop(0, num_passes, one_pass, c0)

    base = (pl.program_id(0) * (G * m)).astype(jnp.uint32)
    gidx = (base + jax.lax.broadcasted_iota(jnp.uint32, (G, m), 0) * m +
            (c & jnp.uint32(m - 1)))
    idx_mask = jnp.uint32((1 << idx_bits) - 1)
    if unpack:
        o_ref[...] = jnp.where(gidx < n, gidx, idx_mask).astype(jnp.int32)
    else:
        packed = ((c >> lb) << idx_bits) | gidx
        o_ref[...] = jnp.where(gidx < n, packed, jnp.uint32(SENTINEL))


def _block_imap(i):
    return (i, 0)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def radix_tile_sort(x: jnp.ndarray, *, tile: int = 1024, total_bits: int = 32,
                    digit_bits: int = 4, key_shift: int = 0, group: int = 8,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort each tile of a (n,) uint32 array by the ``total_bits`` bits at
    ``key_shift`` — stable, so tie order (bits outside the range) is
    preserved.  Drop-in replacement for the bitonic ``tile_sort``;
    ``ceil(total_bits / digit_bits)`` passes run inside one launch."""
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    tile = min(tile, n)
    _check_tile(tile, digit_bits)
    assert n % tile == 0
    nt = n // tile
    g = _pick_group(nt, group)
    passes = digit_passes(total_bits, digit_bits, key_shift=key_shift)
    kernel = functools.partial(_radix_sort_kernel, num_passes=len(passes),
                               digit_bits=digit_bits, sort_bits=total_bits,
                               key_shift=key_shift)
    record("tile_sort", (nt // g,), [(g, tile)])
    out = pl.pallas_call(
        kernel,
        grid=(nt // g,),
        in_specs=[pl.BlockSpec((g, tile), _block_imap)],
        out_specs=pl.BlockSpec((g, tile), _block_imap),
        out_shape=jax.ShapeDtypeStruct((nt, tile), x.dtype),
        interpret=interpret,
    )(x.reshape(nt, tile))
    return out.reshape(n)


def radix_tile_sort_packed(keys: jnp.ndarray, *, n: int, tile: int,
                           num_key_bits: int, idx_bits: int,
                           digit_bits: int = 4, group: int = 8,
                           unpack: bool = False, passes=None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused pack + tile sort: raw int32 keys (padded to a multiple of
    ``tile``; pad rows must carry the max key) → per-tile-sorted packed
    uint32 words ``key << idx_bits | global_index``, pad slots as the
    sentinel.  With ``unpack=True`` (single-tile pipelines) the kernel
    emits the int32 order directly — zero standalone elementwise launches
    on either side.  ``passes`` takes the plan's
    :meth:`~repro.core.plan.Plan.sort_schedule` digit-pass tuple and is
    what actually parameterizes the kernel (pass count, digit stride and
    ranked bit-width all come from it; derived locally when absent)."""
    interpret = resolve_interpret(interpret)
    n_pad = keys.shape[0]
    tile = min(tile, n_pad)
    assert n_pad % tile == 0
    nt = n_pad // tile
    g = _pick_group(nt, group)
    lb = tile.bit_length() - 1
    if passes is None:
        passes = digit_passes(num_key_bits, digit_bits, key_shift=lb)
    passes = tuple(passes)
    _check_tile(tile, passes[0].bits if passes else digit_bits)
    if passes and passes[0].shift != lb:
        # layout invariant, not arithmetic: the composite places the key
        # at bit log2(tile), so the schedule's key_shift must agree
        raise ValueError(f"schedule key_shift {passes[0].shift} != "
                         f"log2(tile) = {lb}")
    # the kernel strides uniformly by passes[0].bits (only the final pass
    # may narrow) — reject any other shape instead of silently mis-sorting
    for i, p in enumerate(passes):
        if p.shift != passes[0].shift + i * passes[0].bits or \
                (p.bits != passes[0].bits and i != len(passes) - 1) or \
                p.bits > passes[0].bits:
            raise ValueError(
                f"passes must be contiguous with uniform stride (last may "
                f"narrow), got {passes}")
    kernel = functools.partial(
        _fused_tile_sort_kernel, n=n, num_key_bits=num_key_bits,
        idx_bits=idx_bits, num_passes=len(passes),
        digit_bits=passes[0].bits if passes else digit_bits,
        sort_bits=sum(p.bits for p in passes), unpack=unpack)
    out_dtype = jnp.int32 if unpack else jnp.uint32
    record("tile_sort", (nt // g,), [(g, tile)])
    out = pl.pallas_call(
        kernel,
        grid=(nt // g,),
        in_specs=[pl.BlockSpec((g, tile), _block_imap)],
        out_specs=pl.BlockSpec((g, tile), _block_imap),
        out_shape=jax.ShapeDtypeStruct((nt, tile), out_dtype),
        interpret=interpret,
    )(keys.reshape(nt, tile))
    return out.reshape(n_pad)


# ---------------------------------------------------------------------------
# multi-tile LSD radix (PR 6 tentpole): kill the merge tree
#
# The merge-tree argsort pays 1 + log2(n/tile) launches.  A *global* LSD
# radix pays 3·ceil(num_key_bits / digit_bits) — independent of n:
#
#   per digit pass
#     1. local:   per-tile stable sort by the pass digit + per-tile digit
#                 histogram (one grid launch, the PR 4 rank machinery)
#     2. scan:    exclusive scan of the (num_tiles × R) histogram matrix
#                 flattened digit-major → global digit base offsets
#                 (ONE launch regardless of num_tiles — tile_scan.py's
#                 cross-tile VMEM carry)
#     3. scatter: after the local sort each (tile, digit) segment is
#                 contiguous in BOTH source and destination, so global
#                 placement is R masked fixed-size window copies per tile
#                 at dynamic offsets — no 1-D gathers, TPU-lowerable
#
# Stability: only the key digit bits are ranked; the packed index bits ride
# below them, so LSD stability orders equal keys by global index for free.
# Pad keys carry the max key and land at the global tail.
# ---------------------------------------------------------------------------

def _mt_local_kernel(x_ref, o_ref, h_ref, *, shift, bits, pack, idx_bits):
    """One digit pass, tile-local half: stable sort of each tile by the
    ``bits``-wide digit at ``shift`` plus the per-tile digit histogram.
    With ``pack`` (first pass) the input is raw int32 keys and the kernel
    emits ``key << idx_bits | global_index`` words — the pack launch is
    fused away exactly as in the single-tile pipeline."""
    G, m = x_ref.shape
    rows, cols = _placement_split(m)
    radix = 1 << bits
    if pack:
        base = (pl.program_id(0) * (G * m)).astype(jnp.uint32)
        gidx = (base + jax.lax.broadcasted_iota(jnp.uint32, (G, m), 0) * m +
                jax.lax.broadcasted_iota(jnp.uint32, (G, m), 1))
        c = (x_ref[...].astype(jnp.uint32) << idx_bits) | gidx
    else:
        c = x_ref[...]
    rank, counts = _rank_and_counts(c, jnp.uint32(shift),
                                    jnp.uint32(radix - 1), radix)
    rowoh, coloh = _placement_onehots(rank, rows, cols)
    o_ref[...] = _permute_u32(c, rowoh, coloh)
    h_ref[...] = counts


def _mt_scatter_kernel(x_ref, h_ref, b_ref, o_ref, *, radix, unpack_mask):
    """One digit pass, global half: place every (tile, digit) segment at
    its global base offset.

    Each fori step copies one fixed ``tile``-sized window from the locally
    sorted block into the output at a dynamic offset, masked to the
    segment's true length — lanes past it write back what they read, so
    every real slot is written exactly once with its final value and the
    sequential grid/loop order cannot clobber it.  ``unpack_mask`` (last
    pass) fuses the ``& idx_mask`` unpack in, emitting the int32 order."""
    g, m = x_ref.shape
    h2 = h_ref[...]                                   # (g, R) int32
    ls2 = jnp.cumsum(h2, axis=1) - h2                 # local segment starts
    h = h2.reshape(g * radix)
    lstart = ls2.reshape(g * radix)
    base = b_ref[...].reshape(g * radix)
    xx = x_ref[...].reshape(g * m)
    if unpack_mask is not None:
        xx = (xx & jnp.uint32(unpack_mask)).astype(jnp.int32)
    # segment reads may run past a row end (masked off below) — pad one tile
    xx = jnp.concatenate([xx, jnp.zeros((m,), xx.dtype)])
    idx = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0).reshape(m)

    def body(j, carry):
        cnt = jax.lax.dynamic_index_in_dim(h, j, keepdims=False)
        ls = jax.lax.dynamic_index_in_dim(lstart, j, keepdims=False)
        gb = jax.lax.dynamic_index_in_dim(base, j, keepdims=False)
        row = j // radix
        seg = jax.lax.dynamic_slice(xx, (row * m + ls,), (m,))
        cur = o_ref[pl.ds(gb, m)]
        o_ref[pl.ds(gb, m)] = jnp.where(idx < cnt, seg, cur)
        return carry

    jax.lax.fori_loop(0, g * radix, body, 0)


def _mt_local(x, *, nt, tile, shift, bits, pack, idx_bits, group, interpret):
    g = _pick_group(nt, group)
    radix = 1 << bits
    kernel = functools.partial(_mt_local_kernel, shift=shift, bits=bits,
                               pack=pack, idx_bits=idx_bits)
    record("radix_mt_local", (nt // g,), [(g, tile), (g, radix)])
    return pl.pallas_call(
        kernel,
        grid=(nt // g,),
        in_specs=[pl.BlockSpec((g, tile), _block_imap)],
        out_specs=(pl.BlockSpec((g, tile), _block_imap),
                   pl.BlockSpec((g, radix), _block_imap)),
        out_shape=(jax.ShapeDtypeStruct((nt, tile), jnp.uint32),
                   jax.ShapeDtypeStruct((nt, radix), jnp.int32)),
        interpret=interpret,
    )(x.reshape(nt, tile))


def _mt_scatter(local, hist, base, *, tile, radix, group, interpret,
                unpack_mask=None):
    nt = local.shape[0]
    g = _pick_group(nt, group)
    n_pad = nt * tile
    out_dtype = jnp.uint32 if unpack_mask is None else jnp.int32
    kernel = functools.partial(_mt_scatter_kernel, radix=radix,
                               unpack_mask=unpack_mask)
    record("radix_mt_scatter", (nt // g,), [(g, tile), (n_pad + tile,)])
    out = pl.pallas_call(
        kernel,
        grid=(nt // g,),
        in_specs=[pl.BlockSpec((g, tile), _block_imap),
                  pl.BlockSpec((g, radix), _block_imap),
                  pl.BlockSpec((g, radix), _block_imap)],
        # whole-array output, revisited by every grid step (sequential
        # masked RMW); one spare tile keeps the last windows in bounds
        out_specs=pl.BlockSpec((n_pad + tile,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_pad + tile,), out_dtype),
        interpret=interpret,
    )(local, hist, base)
    return out[:n_pad]


def multi_tile_argsort_packed(keys: jnp.ndarray, *, n: int, tile: int,
                              num_key_bits: int, idx_bits: int,
                              digit_bits: int = 4, group: int = 8,
                              scan_block: int = 256, passes=None,
                              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Global stable argsort via multi-tile LSD radix — no merge tree.

    keys: raw int32, padded to a multiple of ``tile`` with the max key (pad
    slots sort to the global tail).  Returns the full padded int32 order;
    callers slice ``[:n]``.  Launches: ``3 · num_passes`` (local + carry
    scan + scatter per digit pass), independent of ``n``; a single-tile
    input degenerates to the fused one-launch tile sort.  ``passes`` takes
    the plan's ``sort_schedule(mode="multi_tile")`` digit passes
    (``key_shift`` must equal ``idx_bits``: digits rank the key bits of the
    packed word, above the index bits)."""
    interpret = resolve_interpret(interpret)
    from .tile_scan import histogram_offsets

    n_pad = keys.shape[0]
    tile = min(tile, n_pad)
    assert n_pad % tile == 0
    nt = n_pad // tile
    if nt == 1:
        return radix_tile_sort_packed(
            keys, n=n, tile=tile, num_key_bits=num_key_bits,
            idx_bits=idx_bits, digit_bits=digit_bits, group=group,
            unpack=True, interpret=interpret)
    if passes is None:
        passes = digit_passes(num_key_bits, digit_bits, key_shift=idx_bits)
    passes = tuple(passes)
    if not passes:
        raise ValueError("multi-tile argsort needs at least one digit pass")
    if passes[0].shift != idx_bits:
        raise ValueError(f"schedule key_shift {passes[0].shift} != "
                         f"idx_bits = {idx_bits}")
    _check_tile(tile, max(p.bits for p in passes))
    idx_mask = (1 << idx_bits) - 1
    x = keys
    for i, p in enumerate(passes):
        local, hist = _mt_local(
            x, nt=nt, tile=tile, shift=p.shift, bits=p.bits, pack=(i == 0),
            idx_bits=idx_bits, group=group, interpret=interpret)
        base = histogram_offsets(hist, block=scan_block, interpret=interpret)
        x = _mt_scatter(
            local, hist, base, tile=tile, radix=1 << p.bits, group=group,
            interpret=interpret,
            unpack_mask=idx_mask if i == len(passes) - 1 else None)
    return x


# ---------------------------------------------------------------------------
# one-launch MoE dispatch: the stable counting sort of expert ids
# ---------------------------------------------------------------------------

def _moe_dispatch_kernel(e_ref, d_ref, base_ref, *, radix):
    """Stable counting-sort destinations of the ``(nt, tile)`` expert ids.

    ``d[t, i]`` = #(ids with a smaller digit anywhere) + #(same digit in
    earlier tiles) + #(same digit earlier in tile ``t``) — where row ``i`` of
    tile ``t`` lands in the expert-sorted order.  The first loop fills the
    same-digit-in-earlier-tiles prefix of every tile (``base_ref``); the
    second ranks each tile.  Counts are one-hot matmuls (a triangular ones
    matrix stands in for a cumsum, which Mosaic does not lower) and stay
    exact in f32: every value is an integer below 2^24, and the matmuls
    whose operands are not 0/1 run at full f32 precision."""
    nt, m = e_ref.shape
    f32 = jnp.float32
    exact = jax.lax.Precision.HIGHEST
    digit = jax.lax.broadcasted_iota(jnp.int32, (radix, m), 0)

    def onehot(t):                                    # (R, tile)
        return (e_ref[pl.ds(t, 1), :] == digit).astype(f32)

    ones = jnp.ones((1, m), f32)

    def count(t, run):
        base_ref[pl.ds(t, 1), :] = run
        return run + jax.lax.dot_general(             # (1, R) histogram
            ones, onehot(t), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)

    total = jax.lax.fori_loop(0, nt, count, jnp.zeros((1, radix), f32))
    below = (jax.lax.broadcasted_iota(jnp.int32, (radix, radix), 0) <
             jax.lax.broadcasted_iota(jnp.int32, (radix, radix), 1))
    smaller = jnp.dot(total, below.astype(f32), precision=exact,
                      preferred_element_type=f32)     # (1, R)
    upto = (jax.lax.broadcasted_iota(jnp.int32, (m, m), 0) <=
            jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)).astype(f32)

    def place(t, carry):
        oh = onehot(t)
        seen = jnp.dot(oh, upto, preferred_element_type=f32)   # (R, tile)
        within = jnp.sum(oh * seen, axis=0, keepdims=True) - 1.0
        base = jnp.dot(base_ref[pl.ds(t, 1), :] + smaller, oh,
                       precision=exact, preferred_element_type=f32)
        d_ref[pl.ds(t, 1), :] = (within + base).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, nt, place, 0)


def _moe_dispatch_impl(e, *, radix, interpret):
    nt, tile = e.shape
    record("moe_dispatch", (1,), [(nt, tile)])
    return pl.pallas_call(
        functools.partial(_moe_dispatch_kernel, radix=radix),
        out_shape=jax.ShapeDtypeStruct((nt, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((nt, radix), jnp.float32)],
        interpret=interpret,
    )(e)


_moe_dispatch_jitted = functools.partial(
    jax.jit, static_argnames=("radix", "interpret"))(_moe_dispatch_impl)


def moe_dispatch_sort(x: jnp.ndarray, experts: jnp.ndarray,
                      probs: jnp.ndarray, *, num_experts: int,
                      tile: int = 512, interpret: Optional[bool] = None,
                      jit: bool = True):
    """One-``pallas_call`` MoE routing: the stable sort of the (T·K,)
    expert assignments by expert id is a single kernel launch at any T,
    followed by the permutation's row gathers.

    x: (T, D) activations; experts/probs: (T, K) from ``route_topk``.
    Returns ``(xd (T·K, D), sorted_e, sorted_tok, sorted_p)`` — bit-identical
    to the stable argsort + gather path (a permutation moves every value
    unchanged).  The kernel holds only the ids and their destinations, so
    its VMEM footprint does not grow with ``D``.  Requires
    ``num_experts ≤ 256`` (one ≤ 9-bit digit; the sentinel digit ``E``
    marks pad rows, which sort to the tail and are sliced off).
    """
    interpret = resolve_interpret(interpret)
    T, _ = x.shape
    K = experts.shape[-1]
    E = num_experts
    if E > 256:
        raise ValueError(f"one-launch dispatch needs num_experts ≤ 256, "
                         f"got {E} (fall back to argsort + gather)")
    n = T * K
    radix = 1 << max(1, math.ceil(math.log2(E + 1)))   # digit E = pad
    tile = min(tile, 1 << max(1, math.ceil(math.log2(max(2, n)))))
    n_pad = -(-n // tile) * tile

    flat_e = experts.reshape(n).astype(jnp.int32)
    e = jnp.concatenate([flat_e, jnp.full((n_pad - n,), E, jnp.int32)])
    fn = _moe_dispatch_jitted if jit else _moe_dispatch_impl
    dest = fn(e.reshape(n_pad // tile, tile), radix=radix,
              interpret=interpret).reshape(n_pad)[:n]
    order = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    sorted_tok = order // K
    return (x[sorted_tok], flat_e[order], sorted_tok,
            probs.reshape(n)[order])


__all__ = ["radix_tile_sort", "radix_tile_sort_packed",
           "multi_tile_argsort_packed", "moe_dispatch_sort", "SENTINEL"]
