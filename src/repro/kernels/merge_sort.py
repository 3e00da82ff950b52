"""Pallas stable merge sort — the paper's §3.7 showcase, deployed for MoE
token dispatch.

Structure mirrors Kvik's sort, batched level-by-level for a compiled target
(full design note: ``src/repro/kernels/DESIGN.md``):

  1. the input is divided into tiles by a Kvik plan
     (``even_levels(bound_depth(...))``), whose
     :meth:`~repro.core.plan.Plan.sort_schedule` also carries the radix
     digit-pass metadata for the tile phase,
  2. each tile is sorted locally by an **in-kernel LSD radix sort**
     (``radix_sort.py``: r-bit digit passes, masked-cumsum ranks, one-hot
     matmul placement — no 1-D gathers; the seed's bitonic network kernel
     remains available as ``tile_sort`` / ``method="bitonic"``),
  3. sorted runs are fused pairwise, **one ``pallas_call`` per merge
     level**: ``grid=(num_pairs, blocks_per_pair)`` with merge-path
     (diagonal co-rank binary search) partitioning, ≤ 2·tile VMEM per
     program, ``log2(n/tile)`` launches total.  The kernel is lowered for
     real TPUs: 2-D ``(8, tile//8)`` blocks and the per-block ``la``
     co-rank scalar delivered in SMEM via ``PrefetchScalarGridSpec``
     (interpreted off the TPU, see ``kernels.resolve_interpret``).

Stability: keys are packed as ``key << idx_bits | index`` into uint32 —
equal keys order by original index.  ``idx_bits`` is derived per call as
``ceil(log2(n))`` (``IDX_BITS = 20`` is the documented default cap), so
small batches admit keys up to ``2^(32 − ceil(log2(n)))``.  On the default
fused path the pack and the final ``& idx_mask`` unpack live *inside* the
first tile-sort and last merge-level kernels — ``argsort(jit=True)`` runs
zero standalone elementwise launches (``fused=False`` reconstructs them as
separate pack/unpack kernels for comparison; ``trace_launches`` shows the
two-launch drop).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import SeqWork, bound_depth, build_plan, even_levels
from . import resolve_interpret
from .launch_trace import LaunchRecord, record, trace_launches
from .radix_sort import (SENTINEL, multi_tile_argsort_packed,  # noqa: F401 —
                         radix_tile_sort,                # SENTINEL re-export
                         radix_tile_sort_packed)

IDX_BITS = 20                 # documented default cap: tiles up to 2^20
IDX_MASK = (1 << IDX_BITS) - 1


def _pallas_call(kernel, *, kind: str, grid, in_specs, out_specs, out_shape,
                 interpret):
    record(kind, grid,
           [s.block_shape for s in in_specs] + [out_specs.block_shape])
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)


# ---------------------------------------------------------------------------
# bitonic building blocks (pure jnp — used inside kernel bodies)
# ---------------------------------------------------------------------------

def _compare_exchange(x: jnp.ndarray, j: int, k: int) -> jnp.ndarray:
    """One bitonic stage via reshape/stride swaps — no gathers.

    Pairing (i, i^j) with i's j-bit clear is exactly the (row, lane) split of
    a ``(m/2j, 2, j)`` view; the direction bit ``i & k`` is constant per row
    because ``k ≥ 2j`` in every stage of the network.
    """
    m = x.shape[0]
    y = x.reshape(m // (2 * j), 2, j)
    a, b = y[:, 0, :], y[:, 1, :]
    lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
    row = jax.lax.broadcasted_iota(jnp.int32, (m // (2 * j), 1), 0)
    up = ((row * (2 * j)) & k) == 0
    return jnp.stack([jnp.where(up, lo, hi), jnp.where(up, hi, lo)],
                     axis=1).reshape(m)


def _bitonic_sort_network(x: jnp.ndarray) -> jnp.ndarray:
    """Full ascending bitonic sort of a power-of-two 1-D array."""
    n = x.shape[0]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            x = _compare_exchange(x, j, k)
            j //= 2
        k *= 2
    return x


def _bitonic_merge_network(x: jnp.ndarray) -> jnp.ndarray:
    """Monotonic merge of a bitonic input (ascending result).  All stages run
    ascending (``k = n``), so the direction select drops out entirely."""
    m = x.shape[0]
    j = m // 2
    while j >= 1:
        y = x.reshape(m // (2 * j), 2, j)
        a, b = y[:, 0, :], y[:, 1, :]
        x = jnp.stack([jnp.minimum(a, b), jnp.maximum(a, b)],
                      axis=1).reshape(m)
        j //= 2
    return x


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _tile_sort_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_sort_network(x_ref[...])


def _pack_kernel(k_ref, o_ref, *, n, idx_bits):
    """Standalone elementwise pack launch (the ``fused=False`` path):
    ``key << idx_bits | index``, pad slots (index ≥ n) to the sentinel."""
    m = k_ref.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.uint32, (m, 1), 0).reshape(m)
    packed = (k_ref[...].astype(jnp.uint32) << idx_bits) | idx
    o_ref[...] = jnp.where(idx < n, packed, jnp.uint32(SENTINEL))


def _unpack_kernel(x_ref, o_ref, *, idx_mask):
    """Standalone elementwise unpack launch (the ``fused=False`` path)."""
    o_ref[...] = (x_ref[...] & jnp.uint32(idx_mask)).astype(jnp.int32)


def _elementwise_imap(i):
    return (0,)


def _pack(keys: jnp.ndarray, *, n: int, idx_bits: int,
          interpret: bool) -> jnp.ndarray:
    m = keys.shape[0]
    return _pallas_call(
        functools.partial(_pack_kernel, n=n, idx_bits=idx_bits),
        kind="pack", grid=(1,),
        in_specs=[pl.BlockSpec((m,), _elementwise_imap)],
        out_specs=pl.BlockSpec((m,), _elementwise_imap),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.uint32),
        interpret=interpret)(keys)


def _unpack(x: jnp.ndarray, *, idx_mask: int, interpret: bool) -> jnp.ndarray:
    m = x.shape[0]
    return _pallas_call(
        functools.partial(_unpack_kernel, idx_mask=idx_mask),
        kind="unpack", grid=(1,),
        in_specs=[pl.BlockSpec((m,), _elementwise_imap)],
        out_specs=pl.BlockSpec((m,), _elementwise_imap),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.int32),
        interpret=interpret)(x)


def _merge_level_kernel(la_ref, a_ref, b_ref, o_ref, *, nb, unpack_mask):
    """Merge one fixed tile-sized output block of one run pair.

    ``a_ref``/``b_ref`` hold the merge-path windows for this block (≤ tile
    valid elements each, ``la`` of them from A); positions past the valid
    length are masked to the sentinel, the concat(A, reverse(B)) sequence is
    bitonic, and a gather-free bitonic merge finishes the block.  ``la`` is
    a scalar-prefetch input (SMEM on a real TPU): the whole co-rank table
    is available before the body runs, indexed by program id.  Blocks are
    2-D ``(8, tile//8)`` (sublane, lane) when the tile allows.  With
    ``unpack_mask`` set (last level of a fused argsort) the block is
    unpacked to the int32 order in-kernel.
    """
    shape = a_ref.shape
    tile = math.prod(shape)
    la = la_ref[pl.program_id(0) * nb + pl.program_id(1)]
    idx = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0).reshape(tile)
    a = jnp.where(idx < la, a_ref[...].reshape(tile), jnp.uint32(SENTINEL))
    b = jnp.where(idx < tile - la, b_ref[...].reshape(tile),
                  jnp.uint32(SENTINEL))
    merged = _bitonic_merge_network(jnp.concatenate([a, b[::-1]]))[:tile]
    if unpack_mask is not None:
        merged = (merged & jnp.uint32(unpack_mask)).astype(jnp.int32)
    o_ref[...] = merged.reshape(shape)


def tile_sort(x: jnp.ndarray, *, tile: int = 1024,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort each tile of a (n,) uint32 array locally with the bitonic
    network (the seed kernel — kept as the radix baseline and fallback).
    n % tile == 0."""
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    tile = min(tile, n)
    assert n % tile == 0 and (tile & (tile - 1)) == 0
    nt = n // tile
    return _pallas_call(
        _tile_sort_kernel,
        kind="tile_sort",
        grid=(nt,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# merge-path partitioning (driver-side, vectorized over every output block)
# ---------------------------------------------------------------------------

def _merge_path_starts(ab: jnp.ndarray, run: int, tile: int):
    """Co-rank split of every output diagonal of every run pair.

    ab: (num_pairs, 2, run) sorted runs.  For each pair and each diagonal
    ``d = b*tile`` (b = 0..2·run/tile), binary-search the smallest ``ia``
    with ``A[ia] > B[d-1-ia]`` — the count of A elements among the first
    ``d`` elements of the stable merge (ties go to A).  Returns
    ``(a_start, b_start, la)``, each (num_pairs, blocks_per_pair) int32.
    """
    num_pairs = ab.shape[0]
    nb = (2 * run) // tile
    a_run, b_run = ab[:, 0, :], ab[:, 1, :]
    d = jnp.arange(nb + 1, dtype=jnp.int32) * tile                 # (nb+1,)
    lo = jnp.broadcast_to(jnp.maximum(0, d - run), (num_pairs, nb + 1))
    hi = jnp.broadcast_to(jnp.minimum(d, run), (num_pairs, nb + 1))
    steps = max(1, run).bit_length() + 1

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) // 2
        a_mid = jnp.take_along_axis(a_run, jnp.clip(mid, 0, run - 1), axis=1)
        b_idx = jnp.clip(d[None, :] - 1 - mid, 0, run - 1)
        b_val = jnp.take_along_axis(b_run, b_idx, axis=1)
        go_right = a_mid <= b_val          # A[mid] within the first d merged
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    ia, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    a_start = ia[:, :-1]
    la = ia[:, 1:] - ia[:, :-1]
    b_start = d[None, :-1] - a_start
    return a_start, b_start, la


def _extract_windows(runs: jnp.ndarray, start: jnp.ndarray,
                     tile: int) -> jnp.ndarray:
    """Fixed tile-sized windows of each run at per-block start offsets.

    runs: (num_pairs, run), start: (num_pairs, nb) → (num_pairs, nb, tile).
    Reads past the run end are clamped; the kernel masks them out via ``la``.
    """
    num_pairs, run = runs.shape
    nb = start.shape[1]
    idx = start[:, :, None] + jnp.arange(tile, dtype=jnp.int32)[None, None, :]
    idx = jnp.minimum(idx, run - 1)
    src = jnp.broadcast_to(runs[:, None, :], (num_pairs, nb, run))
    return jnp.take_along_axis(src, idx, axis=2)


def _window_imap_2d(p, b, la):
    return (p, b, 0, 0)


def _window_imap_1d(p, b, la):
    return (p, b, 0)


def _merge_level(x: jnp.ndarray, *, run: int, tile: int, interpret: bool,
                 unpack_mask: Optional[int] = None) -> jnp.ndarray:
    """Merge all adjacent (2·run)-pairs of sorted runs in one pallas_call.

    Real-TPU lowering: window blocks are 2-D ``(8, tile//8)`` (sublane,
    lane) whenever ``tile % 8 == 0``, and the per-block ``la`` co-rank
    table travels as a scalar-prefetch operand (SMEM) instead of a blocked
    VMEM input.  ``unpack_mask`` fuses the final ``& idx_mask`` unpack of
    ``argsort`` into this launch (int32 output).
    """
    n = x.shape[0]
    assert n % (2 * run) == 0 and run % tile == 0
    num_pairs = n // (2 * run)
    nb = (2 * run) // tile                       # output blocks per pair
    ab = x.reshape(num_pairs, 2, run)
    a_start, b_start, la = _merge_path_starts(ab, run, tile)
    a_win = _extract_windows(ab[:, 0, :], a_start, tile)
    b_win = _extract_windows(ab[:, 1, :], b_start, tile)
    if tile % 8 == 0:
        block = (1, 1, 8, tile // 8)
        imap = _window_imap_2d
        a_win = a_win.reshape(num_pairs, nb, 8, tile // 8)
        b_win = b_win.reshape(num_pairs, nb, 8, tile // 8)
    else:
        block = (1, 1, tile)
        imap = _window_imap_1d
    out_dtype = jnp.uint32 if unpack_mask is None else jnp.int32
    kernel = functools.partial(_merge_level_kernel, nb=nb,
                               unpack_mask=unpack_mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_pairs, nb),
        in_specs=[pl.BlockSpec(block, imap), pl.BlockSpec(block, imap)],
        out_specs=pl.BlockSpec(block, imap),
    )
    record("merge_level", (num_pairs, nb), [block, block, block])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(a_win.shape, out_dtype),
        interpret=interpret,
    )(la.reshape(-1).astype(jnp.int32), a_win, b_win)
    return out.reshape(n)


def merge_pair(a: jnp.ndarray, b: jnp.ndarray, *, tile: int = 1024,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Merge two sorted arrays of equal power-of-two length.

    Compatibility wrapper: one num_pairs=1 level of the level-batched
    merge-path kernel.
    """
    interpret = resolve_interpret(interpret)
    n = a.shape[0]
    return _merge_level(jnp.concatenate([a, b]), run=n, tile=min(tile, n),
                        interpret=interpret)


# ---------------------------------------------------------------------------
# composed sort (tile plan + level-batched merge schedule)
# ---------------------------------------------------------------------------

def _tile_plan(n: int, tile: int):
    """The Kvik plan driving the sort: ``even_levels(bound_depth(...))``
    over the index range.  even_levels parity is realized on the tile count
    (halve the tile once so the level count is even).  Returns
    ``(plan, depth, tile)``; plan is None when depth == 0."""
    tile = min(tile, n)
    depth = int(math.log2(n // tile))
    parity_ok = depth % 2 == 0
    if not parity_ok and tile >= 2:
        depth += 1          # even merge parity — the paper's even_levels
        tile = n >> depth   # concern, realized on the tile count
        parity_ok = True
    if depth == 0:
        return None, 0, tile
    # tile == 1 with odd depth cannot be re-tiled; run the odd schedule
    # rather than let even_levels force division below one element
    work = bound_depth(SeqWork(0, n, align=tile, min_size=tile), depth)
    plan = build_plan(even_levels(work) if parity_ok else work)
    return plan, depth, tile


def sort_u32(x: jnp.ndarray, *, tile: int = 1024,
             interpret: Optional[bool] = None,
             method: str = "radix", total_bits: int = 32,
             digit_bits: int = 4, group: int = 8) -> jnp.ndarray:
    """Stable-ready sort of packed uint32 keys: tile sort, then one launch
    per merge level of the plan's schedule.

    The tile phase defaults to the in-kernel LSD radix sort
    (``ceil(total_bits / digit_bits)`` digit passes — pass ``total_bits``
    when the packed width is known, e.g. ``num_key_bits + idx_bits``);
    ``method="bitonic"`` keeps the seed's O(m·log²m) network.
    """
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    if n & (n - 1):
        raise ValueError(f"sort_u32 needs a power-of-two input, got n={n} "
                         "(pad first)")
    plan, depth, tile = _tile_plan(n, tile)
    if method == "radix":
        x = radix_tile_sort(x, tile=tile, total_bits=total_bits,
                            digit_bits=digit_bits, group=group,
                            interpret=interpret)
    elif method == "bitonic":
        x = tile_sort(x, tile=tile, interpret=interpret)
    else:
        raise ValueError(f"unknown tile-sort method {method!r}")
    if depth == 0:
        return x
    schedule = plan.merge_schedule()
    assert len(schedule) == depth
    for level in schedule:
        assert level.uniform, "sort plan must divide into uniform runs"
        x = _merge_level(x, run=level.run_length, tile=tile,
                         interpret=interpret)
    return x


def _argsort_impl(keys: jnp.ndarray, *, n: int, n_pad: int, tile: int,
                  interpret: bool, num_key_bits: int, idx_bits: int,
                  method: str, fused: bool, digit_bits: int,
                  group: int, strategy: str = "merge") -> jnp.ndarray:
    idx_mask = (1 << idx_bits) - 1
    if strategy == "multi_tile":
        # merge-tree-free path: 3 launches per digit pass (local sort +
        # histogram, cross-tile carry scan, global scatter), independent of
        # n.  n_pad is any multiple of the tile — no power-of-two padding.
        tile_mt = min(tile, n_pad)
        nt = n_pad // tile_mt
        if n_pad != n:
            pad = jnp.full((n_pad - n,), (1 << num_key_bits) - 1, keys.dtype)
            keys = jnp.concatenate([keys, pad])
        passes = None
        if nt > 1 and (nt & (nt - 1)) == 0:
            # power-of-two tile counts route through the plan so the
            # schedule metadata (mode, num_tiles, num_launches) is exercised
            depth = int(math.log2(nt))
            work = bound_depth(SeqWork(0, n_pad, align=tile_mt,
                                       min_size=tile_mt), depth)
            sched = build_plan(work).sort_schedule(
                sort_bits=num_key_bits, digit_bits=digit_bits,
                key_shift=idx_bits, mode="multi_tile")
            passes = sched.tile_passes
        return multi_tile_argsort_packed(
            keys, n=n, tile=tile_mt, num_key_bits=num_key_bits,
            idx_bits=idx_bits, digit_bits=digit_bits, group=group,
            passes=passes, interpret=interpret)[:n]
    plan, depth, tile = _tile_plan(n_pad, tile)
    if fused:
        # pack lives in the tile-sort kernel; pad keys carry the max key so
        # they sort to the tile end (the kernel emits sentinels for them)
        if n_pad != n:
            pad = jnp.full((n_pad - n,), (1 << num_key_bits) - 1, keys.dtype)
            keys = jnp.concatenate([keys, pad])
        schedule = (plan.sort_schedule(sort_bits=num_key_bits,
                                       digit_bits=digit_bits,
                                       key_shift=int(math.log2(tile)))
                    if plan is not None else None)
        x = radix_tile_sort_packed(
            keys, n=n, tile=tile, num_key_bits=num_key_bits,
            idx_bits=idx_bits, digit_bits=digit_bits, group=group,
            unpack=depth == 0, interpret=interpret,
            passes=schedule.tile_passes if schedule is not None else None)
        if depth == 0:
            return x[:n]
        levels = schedule.levels
        for i, level in enumerate(levels):
            assert level.uniform, "sort plan must divide into uniform runs"
            x = _merge_level(
                x, run=level.run_length, tile=tile, interpret=interpret,
                unpack_mask=idx_mask if i == len(levels) - 1 else None)
        return x[:n]
    # unfused: standalone pack/unpack launches around the plain u32 sort
    if n_pad != n:
        keys = jnp.concatenate(
            [keys, jnp.zeros((n_pad - n,), keys.dtype)])
    packed = _pack(keys, n=n, idx_bits=idx_bits, interpret=interpret)
    out = sort_u32(packed, tile=tile, interpret=interpret, method=method,
                   total_bits=num_key_bits + idx_bits, digit_bits=digit_bits,
                   group=group)
    return _unpack(out, idx_mask=idx_mask, interpret=interpret)[:n]


_ARGSORT_STATICS = ("n", "n_pad", "tile", "interpret", "num_key_bits",
                    "idx_bits", "method", "fused", "digit_bits", "group",
                    "strategy")


@functools.partial(jax.jit, static_argnames=_ARGSORT_STATICS)
def _argsort_jitted(keys, **kw):
    return _argsort_impl(keys, **kw)


def argsort(keys: jnp.ndarray, *, num_key_bits: int = 12, tile: int = 1024,
            interpret: Optional[bool] = None, jit: bool = False,
            method: str = "radix",
            fused: Optional[bool] = None, digit_bits: int = 4,
            group: int = 8, strategy: Optional[str] = None) -> jnp.ndarray:
    """Stable argsort of small-integer keys (expert ids) — MoE dispatch entry.

    keys: (n,) int32 with values in [0, 2^num_key_bits).
    ``idx_bits = ceil(log2(n))`` is derived per call, so the hard error only
    fires when ``num_key_bits + idx_bits > 32`` — packing genuinely cannot
    fit (``IDX_BITS = 20`` is the documented default: the cap at the default
    ``num_key_bits=12``).

    ``strategy`` picks the global combine:

    * ``"multi_tile"`` (the default for small keys): multi-tile LSD radix —
      3 launches per digit pass (tile-local sort + histogram, cross-tile
      carry scan, global scatter), so the launch count depends only on
      ``num_key_bits``, not ``n``.  Input is padded to a multiple of the
      tile (pad keys sort to the end and are dropped).
    * ``"merge"``: the PR 2–4 merge tree — fused radix tile sort, then one
      launch per merge level (``log2(n/tile)``).  Auto-selected for wide
      keys (``num_key_bits > 16``), where ``ceil(bits/digit_bits)`` radix
      passes over the whole array would cost more launches and more data
      movement than the tree; also the only strategy for ``fused=False`` /
      ``method="bitonic"`` comparison pipelines.  Pads to a power of two.

    Both strategies are stable sorts of the same keys, so their outputs are
    bit-identical.  With ``jit=True`` the whole pipeline runs as one
    compiled program, cached per shape/config.
    """
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    if fused is None:
        fused = method == "radix"
    if fused and method != "radix":
        raise ValueError("fused pack/unpack requires method='radix' "
                         "(the bitonic network kernel is the unfused "
                         "baseline)")
    if strategy is None:
        strategy = ("multi_tile" if fused and method == "radix"
                    and num_key_bits <= 16 else "merge")
    if strategy not in ("merge", "multi_tile"):
        raise ValueError(f"unknown argsort strategy {strategy!r}")
    if strategy == "multi_tile" and (not fused or method != "radix"):
        raise ValueError("strategy='multi_tile' requires the fused radix "
                         "pipeline (method='radix', fused=True)")
    idx_bits = max(1, (n - 1).bit_length()) if n else 1
    if num_key_bits + idx_bits > 32:
        raise ValueError(
            f"cannot pack: num_key_bits={num_key_bits} + idx_bits="
            f"{idx_bits} (= ceil(log2(n)) for n={n}) exceeds 32 — packed "
            "keys and indices would collide.  Shrink the batch or the key "
            f"range (n={n} admits keys up to 2^{32 - idx_bits})")
    if not isinstance(keys, jax.core.Tracer):
        kmax = int(jnp.max(keys)) if n else 0
        if kmax >= 1 << num_key_bits:
            raise ValueError(
                f"keys must be < 2^num_key_bits = {1 << num_key_bits}, got "
                f"max key {kmax}: packed keys would collide with the index "
                "bits and silently corrupt the order (raise num_key_bits)")
    if strategy == "multi_tile":
        # any whole number of tiles works — no power-of-two padding
        t_eff = min(tile, 1 << math.ceil(math.log2(max(2, n))))
        n_pad = -(-max(2, n) // t_eff) * t_eff
    else:
        n_pad = 1 << math.ceil(math.log2(max(2, n)))
    fn = _argsort_jitted if jit else _argsort_impl
    return fn(jnp.asarray(keys), n=n, n_pad=n_pad, tile=tile,
              interpret=interpret, num_key_bits=num_key_bits,
              idx_bits=idx_bits, method=method, fused=fused,
              digit_bits=digit_bits, group=group, strategy=strategy)


__all__ = ["argsort", "sort_u32", "tile_sort", "merge_pair",
           "trace_launches", "LaunchRecord", "IDX_BITS", "IDX_MASK"]
