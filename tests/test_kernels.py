"""Per-kernel allclose tests: shape/dtype sweeps against the jnp oracles."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels import ref
from repro.kernels import merge_sort
from repro.kernels import radix_sort
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import combine_partials, flash_decode
from repro.kernels.merge_sort import argsort, merge_pair, sort_u32, tile_sort


def rnd(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) \
        .astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 4, 2, 64),      # GQA 2:1
    (1, 512, 8, 2, 32),      # GQA 4:1
    (2, 128, 6, 1, 128),     # MQA-ish, hd=128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, hd, dtype, causal):
    q = rnd(0, (B, S, H, hd), dtype)
    k = rnd(1, (B, S, KV, hd), dtype)
    v = rnd(2, (B, S, KV, hd), dtype)
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                        interpret=True)
    o_ref = ref.attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=8 * TOL[dtype], rtol=8 * TOL[dtype])


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_invariance(bq, bk):
    q = rnd(0, (1, 256, 2, 64), jnp.float32)
    k = rnd(1, (1, 256, 2, 64), jnp.float32)
    v = rnd(2, (1, 256, 2, 64), jnp.float32)
    o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                        interpret=True)
    o_ref = ref.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-4)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,bk", [
    (2, 512, 4, 2, 64, 128),
    (1, 1024, 8, 8, 64, 256),
    (3, 256, 4, 1, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, S, H, KV, hd, bk, dtype):
    q = rnd(3, (B, H, hd), dtype)
    kc = rnd(4, (B, S, KV, hd), dtype)
    vc = rnd(5, (B, S, KV, hd), dtype)
    lengths = jnp.asarray(
        np.random.RandomState(0).randint(1, S + 1, B), jnp.int32)
    o = flash_decode(q, kc, vc, lengths, block_k=bk, interpret=True)
    o_ref = ref.decode_attention_reference(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=8 * TOL[dtype], rtol=8 * TOL[dtype])


def test_flash_decode_demand_split_invariance():
    """The reduction-tree shape must not change the result (associativity)."""
    q = rnd(6, (2, 4, 64), jnp.float32)
    kc = rnd(7, (2, 1024, 2, 64), jnp.float32)
    vc = rnd(8, (2, 1024, 2, 64), jnp.float32)
    lengths = jnp.asarray([700, 1024], jnp.int32)
    outs = [flash_decode(q, kc, vc, lengths, block_k=128, demand=d,
                         interpret=True) for d in (1, 2, 4, 8)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5)


def test_combine_partials_associative():
    k1, k2, k3 = (rnd(i, (2, 4), jnp.float32) for i in (10, 11, 12))
    a1, a2, a3 = (rnd(i, (2, 4, 8), jnp.float32) for i in (13, 14, 15))
    l1, l2, l3 = (jnp.abs(rnd(i, (2, 4), jnp.float32)) for i in (16, 17, 18))
    p1, p2, p3 = (k1, l1, a1), (k2, l2, a2), (k3, l3, a3)
    left = combine_partials(combine_partials(p1, p2), p3)
    right = combine_partials(p1, combine_partials(p2, p3))
    for a, b in zip(left, right):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# merge sort
# ---------------------------------------------------------------------------

@given(st.integers(1, 4096), st.integers(1, 12), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_argsort_matches_stable_oracle(n, key_bits, seed):
    keys = np.random.RandomState(seed).randint(
        0, 1 << key_bits, n).astype(np.int32)
    order = argsort(jnp.asarray(keys), tile=256, interpret=True)
    expect = ref.stable_argsort_reference(jnp.asarray(keys))
    np.testing.assert_array_equal(np.asarray(order), np.asarray(expect))


@pytest.mark.parametrize("n,tile", [(256, 64), (1024, 256), (4096, 512),
                                    (4096, 1024)])
def test_sort_u32_sorted(n, tile):
    x = jnp.asarray(np.random.RandomState(0).randint(
        0, 2 ** 31, n).astype(np.uint32))
    out = sort_u32(x, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))


def test_tile_sort_sorts_each_tile():
    x = jnp.asarray(np.random.RandomState(1).randint(
        0, 1000, 512).astype(np.uint32))
    out = np.asarray(tile_sort(x, tile=128, interpret=True))
    for t in range(4):
        tile = out[t * 128:(t + 1) * 128]
        assert (np.diff(tile) >= 0).all()


def test_merge_pair_merges():
    a = np.sort(np.random.RandomState(2).randint(0, 1 << 20, 256)) \
        .astype(np.uint32)
    b = np.sort(np.random.RandomState(3).randint(0, 1 << 20, 256)) \
        .astype(np.uint32)
    out = merge_pair(jnp.asarray(a), jnp.asarray(b), interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.sort(np.concatenate([a, b])))


def test_argsort_stability_heavy_duplicates():
    keys = np.zeros(1000, np.int32)          # all equal → order == identity
    order = argsort(jnp.asarray(keys), tile=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(order), np.arange(1000))


# ---------------------------------------------------------------------------
# level-batched merge-path sort (PR 2 tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,tile", [(1 << 12, 256), (1 << 14, 1024),
                                    (1 << 16, 1024)])
def test_merge_tree_launch_count_pinned(n, tile):
    """The merge tree must run in exactly log2(n/tile) pallas_call launches
    (plus the single tile-sort launch) with every merge block ≤ 2·tile
    elements, independent of n — the level-batched structure, pinned."""
    x = jnp.asarray(np.random.RandomState(0).randint(
        0, 2 ** 31, n).astype(np.uint32))
    with merge_sort.trace_launches() as tr:
        out = sort_u32(x, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))
    kinds = [r.kind for r in tr]
    assert kinds.count("tile_sort") == 1
    assert kinds.count("merge_level") == int(math.log2(n // tile))
    assert len(tr) == 1 + int(math.log2(n // tile))
    for rec in tr:
        if rec.kind == "merge_level":
            assert rec.max_block_elems <= 2 * tile
        else:       # radix tile sort groups ≤ 8 tiles per grid cell
            assert rec.max_block_elems <= 8 * tile
    # level L merges 2^L-tile runs: grid=(num_pairs, blocks_per_pair)
    for L, rec in enumerate(r for r in tr if r.kind == "merge_level"):
        run = tile << L
        assert rec.grid == (n // (2 * run), (2 * run) // tile)


def test_merge_level_matches_reference_merge():
    """One level kernel call over several pairs == per-pair np.merge."""
    rng = np.random.RandomState(7)
    tile, run, num_pairs = 64, 256, 4
    runs = np.sort(rng.randint(0, 1 << 30, (num_pairs, 2, run)).astype(
        np.uint32), axis=-1)
    x = jnp.asarray(runs.reshape(-1))
    out = np.asarray(merge_sort._merge_level(
        x, run=run, tile=tile, interpret=True)).reshape(num_pairs, 2 * run)
    for p in range(num_pairs):
        expect = np.sort(np.concatenate([runs[p, 0], runs[p, 1]]))
        np.testing.assert_array_equal(out[p], expect)


def test_merge_path_starts_corank_invariants():
    """Co-rank splits: monotone, diagonal-consistent, and exact on a known
    stable merge (ties go to A)."""
    rng = np.random.RandomState(3)
    run, tile = 128, 32
    a = np.sort(rng.randint(0, 16, run).astype(np.uint32))
    b = np.sort(rng.randint(0, 16, run).astype(np.uint32))
    ab = jnp.asarray(np.stack([a, b])[None])
    a_start, b_start, la = (np.asarray(v) for v in
                            merge_sort._merge_path_starts(ab, run, tile))
    assert a_start.shape == (1, 2 * run // tile)
    # every diagonal splits exactly: a_start + b_start == d, lengths sum tile
    d = np.arange(2 * run // tile) * tile
    np.testing.assert_array_equal(a_start[0] + b_start[0], d)
    assert (la >= 0).all() and (la <= tile).all()
    # exact co-rank: count of A elements among first d of the stable merge
    packed = np.concatenate([a.astype(np.uint64) * 2,       # A before equal B
                             b.astype(np.uint64) * 2 + 1])
    order = np.argsort(packed, kind="stable")
    for i, dd in enumerate(d):
        expect_ia = int((order[:dd] < run).sum())
        assert a_start[0, i] == expect_ia


@pytest.mark.parametrize("n,tile", [(16, 2), (8, 1), (32, 2), (64, 1)])
def test_sort_u32_tiny_tiles_odd_depth(n, tile):
    """Odd merge depth with tiles too small to halve must still sort (the
    parity adjustment falls back to an odd schedule, regression test)."""
    x = np.random.RandomState(n).randint(0, 2 ** 31, n).astype(np.uint32)
    out = np.asarray(sort_u32(jnp.asarray(x), tile=tile, interpret=True))
    np.testing.assert_array_equal(out, np.sort(x))


@pytest.mark.parametrize("n", [1, 2, 3, 5, 255, 257, 1000, 1023, 4097])
@pytest.mark.parametrize("key_bits", [1, 3, 11])
def test_argsort_property_sweep_vs_stable_oracle(n, key_bits):
    """Non-power-of-two sizes × duplicate-heavy keys vs np stable argsort
    (explicit sweep — runs even when hypothesis is stubbed out)."""
    keys = np.random.RandomState(n * 31 + key_bits).randint(
        0, 1 << key_bits, n).astype(np.int32)
    order = argsort(jnp.asarray(keys), tile=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(keys, kind="stable"))


def test_argsort_jit_end_to_end():
    keys = np.random.RandomState(5).randint(0, 64, 777).astype(np.int32)
    order = argsort(jnp.asarray(keys), tile=256, interpret=True, jit=True)
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(keys, kind="stable"))


# ---------------------------------------------------------------------------
# radix tile sort + fused pack/unpack (PR 4 tentpole)
# ---------------------------------------------------------------------------

def _tile_cases(tile, seed=0):
    """Random, all-equal, and reverse-sorted tiles (the radix-vs-bitonic
    equivalence sweep the satellite asks for)."""
    rng = np.random.RandomState(seed)
    rev = np.arange(4 * tile, 0, -1, dtype=np.uint32)
    return {
        "random": rng.randint(0, 2 ** 31, 4 * tile).astype(np.uint32),
        "dup_heavy": rng.randint(0, 7, 4 * tile).astype(np.uint32),
        "all_equal": np.full(4 * tile, 123456, np.uint32),
        "reverse": rev,
    }


@pytest.mark.parametrize("tile", [64, 256, 1024])
@pytest.mark.parametrize("digit_bits", [2, 4, 8])
def test_radix_tile_sort_matches_bitonic(tile, digit_bits):
    """Generic radix tile sort ≡ the bitonic network, bit for bit, on the
    sweep including all-equal and reverse-sorted tiles."""
    for name, x in _tile_cases(tile).items():
        xj = jnp.asarray(x)
        bit = np.asarray(tile_sort(xj, tile=tile, interpret=True))
        rad = np.asarray(radix_sort.radix_tile_sort(
            xj, tile=tile, digit_bits=digit_bits, interpret=True))
        np.testing.assert_array_equal(rad, bit, err_msg=f"case {name}")


def test_radix_tile_sort_packed_rejects_malformed_schedules():
    """The kernel strides uniformly by the first pass width — schedules it
    cannot execute must raise, not silently mis-sort."""
    from repro.core import DigitPass
    keys = jnp.zeros(16, jnp.int32)
    kw = dict(n=16, tile=16, num_key_bits=6, idx_bits=4, interpret=True)
    with pytest.raises(ValueError, match="key_shift"):
        radix_sort.radix_tile_sort_packed(
            keys, passes=(DigitPass(0, 4),), **kw)
    with pytest.raises(ValueError, match="uniform stride"):
        radix_sort.radix_tile_sort_packed(
            keys, passes=(DigitPass(4, 2), DigitPass(6, 4)), **kw)
    with pytest.raises(ValueError, match="uniform stride"):
        radix_sort.radix_tile_sort_packed(
            keys, passes=(DigitPass(4, 4), DigitPass(12, 2)), **kw)
    # the well-formed schedule (narrowed last pass) is accepted
    out = radix_sort.radix_tile_sort_packed(
        keys, passes=(DigitPass(4, 4), DigitPass(8, 2)), **kw)
    assert out.shape == (16,)


def test_radix_tile_sort_respects_bit_window():
    """Bits outside [key_shift, key_shift+total_bits) must not participate
    in the ordering — the final pass narrows to the leftover bits
    (regression: a full-width last-pass digit read them)."""
    # equal low-4-bit digits, differing bit 4: order must be preserved
    x = jnp.asarray(np.asarray([0x10, 0x00], np.uint32))
    out = radix_sort.radix_tile_sort(x, tile=2, total_bits=4, digit_bits=8,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(out), [0x10, 0x00])
    # a shifted window: sort by bits [4, 8) only, low bits are tie order
    vals = np.asarray([0x23, 0x12, 0x21, 0x15], np.uint32)
    out2 = radix_sort.radix_tile_sort(jnp.asarray(vals), tile=4,
                                      total_bits=4, key_shift=4,
                                      digit_bits=3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out2),
                                  [0x12, 0x15, 0x23, 0x21])


@pytest.mark.parametrize("n,tile", [(1024, 256), (4096, 1024)])
def test_fused_radix_tile_sort_matches_pack_plus_bitonic(n, tile):
    """Fused pack+radix tile sort ≡ separate pack followed by the bitonic
    tile sort (bit-identical packed words, sentinel padding included)."""
    idx_bits = max(1, (n - 1).bit_length())
    for name, keys in _tile_cases(tile, seed=3).items():
        keys = (keys[:n] & 0xFFF).astype(np.int32)
        packed = (keys.astype(np.uint32) << idx_bits) | \
            np.arange(n, dtype=np.uint32)
        bit = np.asarray(tile_sort(jnp.asarray(packed), tile=tile,
                                   interpret=True))
        fused = np.asarray(radix_sort.radix_tile_sort_packed(
            jnp.asarray(keys), n=n, tile=tile, num_key_bits=12,
            idx_bits=idx_bits, interpret=True))
        np.testing.assert_array_equal(fused, bit, err_msg=f"case {name}")


def test_argsort_fused_drops_two_elementwise_launches():
    """The fused path must run zero standalone pack/unpack launches — the
    end-to-end launch count drops by exactly those two vs fused=False."""
    keys = jnp.asarray(np.random.RandomState(0).randint(
        0, 16, 4096).astype(np.int32))
    with merge_sort.trace_launches() as tr_fused:
        a = argsort(keys, tile=512, interpret=True, strategy="merge")
    with merge_sort.trace_launches() as tr_unfused:
        b = argsort(keys, tile=512, interpret=True, fused=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    kinds_f = [r.kind for r in tr_fused]
    kinds_u = [r.kind for r in tr_unfused]
    assert "pack" not in kinds_f and "unpack" not in kinds_f
    assert kinds_u.count("pack") == 1 and kinds_u.count("unpack") == 1
    assert len(tr_unfused) - len(tr_fused) == 2
    # and the jitted fused path traces the same zero-elementwise pipeline
    jax.clear_caches()
    with merge_sort.trace_launches() as tr_jit:
        c = argsort(keys, tile=512, interpret=True, jit=True,
                    strategy="merge")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert [r.kind for r in tr_jit] == kinds_f


def test_argsort_methods_agree():
    """radix-fused, radix-unfused, and bitonic argsort agree with the
    stable oracle on a non-power-of-two, duplicate-heavy input."""
    keys = np.random.RandomState(9).randint(0, 5, 3000).astype(np.int32)
    expect = np.argsort(keys, kind="stable")
    for kw in [dict(), dict(fused=False), dict(method="bitonic")]:
        order = argsort(jnp.asarray(keys), tile=256, interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(order), expect,
                                      err_msg=str(kw))


@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 4),
       st.sampled_from([37, 256, 1000, 2048]))
@settings(max_examples=20, deadline=None)
def test_argsort_stability_property(seed, key_bits, n):
    """Property: equal keys preserve input order (dup-heavy distributions:
    at most 16 distinct keys over up to 2048 elements)."""
    keys = np.random.RandomState(seed).randint(
        0, 1 << key_bits, n).astype(np.int32)
    order = np.asarray(argsort(jnp.asarray(keys), num_key_bits=key_bits,
                               tile=256, interpret=True))
    assert (np.sort(order) == np.arange(n)).all()          # a permutation
    sorted_keys = keys[order]
    assert (np.diff(sorted_keys) >= 0).all()               # sorted
    for k in np.unique(keys):                              # stable
        pos = order[sorted_keys == k]
        assert (np.diff(pos) > 0).all(), f"key {k} broke input order"


@pytest.mark.parametrize("dist", ["two_vals", "all_equal", "reverse_blocks"])
def test_argsort_stability_adversarial_distributions(dist):
    n = 2000
    if dist == "two_vals":
        keys = (np.arange(n) % 2).astype(np.int32)
    elif dist == "all_equal":
        keys = np.full(n, 7, np.int32)
    else:
        keys = np.repeat(np.arange(7, -1, -1), 250).astype(np.int32)
    order = np.asarray(argsort(jnp.asarray(keys), num_key_bits=4,
                               tile=256, interpret=True))
    np.testing.assert_array_equal(order, np.argsort(keys, kind="stable"))


def test_argsort_guard_too_many_elements():
    """The hard error fires only when packing is genuinely impossible:
    num_key_bits + ceil(log2(n)) > 32.  At the default num_key_bits=12
    that is exactly n > 2^IDX_BITS = 2^20 (the documented default cap)."""
    n = (1 << merge_sort.IDX_BITS) + 1
    with pytest.raises(ValueError, match="cannot pack"):
        argsort(jnp.zeros(n, jnp.int32))


def test_argsort_guard_key_overflow():
    with pytest.raises(ValueError, match="collide with the index"):
        argsort(jnp.asarray([1, 1 << 4, 3], dtype=jnp.int32), num_key_bits=4)
    # boundary passes: max legal key value sorts fine
    keys = np.asarray([(1 << 4) - 1, 0, (1 << 4) - 1], np.int32)
    order = argsort(jnp.asarray(keys), num_key_bits=4, tile=256,
                    interpret=True)
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(keys, kind="stable"))


def test_argsort_idx_bits_derived_per_call():
    """idx_bits = ceil(log2(n)): small batches admit keys up to
    2^(32 − ceil(log2(n))) — both sides of the boundary pinned."""
    # n=1024 → idx_bits=10 → keys up to 2^22 admissible (would have been
    # rejected under the fixed IDX_BITS=20 packing)
    keys = np.random.RandomState(0).randint(0, 1 << 22, 1024).astype(np.int32)
    order = argsort(jnp.asarray(keys), num_key_bits=22, tile=256,
                    interpret=True)
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(keys, kind="stable"))
    # one element more → idx_bits=11 → 22+11 > 32 → genuinely impossible
    with pytest.raises(ValueError, match="cannot pack"):
        argsort(jnp.zeros(1025, jnp.int32), num_key_bits=22)
    # extreme small-n boundary: two elements admit 31-bit keys…
    keys2 = np.asarray([(1 << 31) - 1, 0], np.int32)
    order2 = argsort(jnp.asarray(keys2), num_key_bits=31, interpret=True)
    np.testing.assert_array_equal(np.asarray(order2), [1, 0])
    # …but three do not (idx_bits=2)
    with pytest.raises(ValueError, match="cannot pack"):
        argsort(jnp.zeros(3, jnp.int32), num_key_bits=31)


# ---------------------------------------------------------------------------
# multi-tile LSD radix (PR 6 tentpole): merge-tree-free global argsort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1 << 13, 1 << 15, 1 << 16])
def test_multi_tile_launch_count_independent_of_n(n):
    """The multi-tile argsort must run exactly 3 launches per digit pass
    (local sort+histogram, carry scan, scatter) at ANY n — launch count a
    function of num_key_bits only, never of n.  Pinned per kind."""
    keys = jnp.asarray(np.random.RandomState(0).randint(
        0, 1 << 12, n).astype(np.int32))
    with merge_sort.trace_launches() as tr:
        out = argsort(keys, tile=1024, interpret=True,
                      strategy="multi_tile")
    np.testing.assert_array_equal(
        np.asarray(out), np.argsort(np.asarray(keys), kind="stable"))
    kinds = [r.kind for r in tr]
    num_passes = 3                       # ceil(12 key bits / 4 digit bits)
    assert kinds == ["radix_mt_local", "tile_scan",
                     "radix_mt_scatter"] * num_passes
    assert len(tr) == 3 * num_passes     # == SortSchedule.num_launches
    for rec in tr:
        if rec.kind in ("radix_mt_local", "radix_mt_scatter"):
            # grouped tile blocks, never whole-array inputs
            assert rec.grid[0] >= max(1, (n // 1024) // 8)


@pytest.mark.parametrize("n", [1 << 12, 3 * 1024, 5000, 1 << 16, 77, 1000])
def test_multi_tile_bit_identical_to_merge_tree(n):
    """Both strategies are stable sorts of the same keys, so the orders
    must be bit-identical — across random / dup-heavy / all-equal /
    reverse inputs including non-power-of-two n."""
    rng = np.random.RandomState(n)
    cases = {
        "random": rng.randint(0, 1 << 12, n).astype(np.int32),
        "dup_heavy": rng.randint(0, 7, n).astype(np.int32),
        "all_equal": np.full(n, (1 << 12) - 1, np.int32),
        "reverse": (np.arange(n, 0, -1) % (1 << 12)).astype(np.int32),
    }
    for name, keys in cases.items():
        jk = jnp.asarray(keys)
        mt = np.asarray(argsort(jk, interpret=True, strategy="multi_tile"))
        mg = np.asarray(argsort(jk, interpret=True, strategy="merge"))
        np.testing.assert_array_equal(mt, mg, err_msg=f"case {name} n={n}")
        np.testing.assert_array_equal(
            mt, np.argsort(keys, kind="stable"), err_msg=f"case {name}")


def test_argsort_strategy_auto_selection():
    """Small keys default to multi_tile; wide keys (> 16 bits) fall back
    to the merge tree; incompatible pipelines are rejected."""
    keys = jnp.asarray(np.random.RandomState(1).randint(
        0, 16, 4096).astype(np.int32))
    with merge_sort.trace_launches() as tr_small:
        argsort(keys, interpret=True)
    assert "radix_mt_local" in {r.kind for r in tr_small}
    wide = jnp.asarray(np.random.RandomState(1).randint(
        0, 1 << 17, 2048).astype(np.int32))
    with merge_sort.trace_launches() as tr_wide:
        argsort(wide, num_key_bits=17, interpret=True)
    kinds = {r.kind for r in tr_wide}
    assert "merge_level" in kinds and "radix_mt_local" not in kinds
    with pytest.raises(ValueError, match="multi_tile"):
        argsort(keys, strategy="multi_tile", fused=False)
    with pytest.raises(ValueError, match="multi_tile"):
        argsort(keys, strategy="multi_tile", method="bitonic")
    with pytest.raises(ValueError, match="strategy"):
        argsort(keys, strategy="quantum")


def test_moe_dispatch_sort_single_launch_and_exact():
    """The fused dispatch kernel: one pallas_call, and every output —
    permuted activation rows included — bit-identical to stable argsort +
    gather."""
    from repro.kernels.radix_sort import moe_dispatch_sort
    rng = np.random.RandomState(7)
    T, K, E, D = 100, 2, 16, 32
    x = rng.randn(T, D).astype(np.float32)
    e = rng.randint(0, E, (T, K)).astype(np.int32)
    p = rng.rand(T, K).astype(np.float32)
    with merge_sort.trace_launches() as tr:
        xd, se, st, sp = moe_dispatch_sort(
            jnp.asarray(x), jnp.asarray(e), jnp.asarray(p),
            num_experts=E, tile=64, jit=False)
    assert [r.kind for r in tr] == ["moe_dispatch"]
    fe, fp = e.reshape(-1), p.reshape(-1)
    tok = np.repeat(np.arange(T), K)
    order = np.argsort(fe, kind="stable")
    np.testing.assert_array_equal(np.asarray(se), fe[order])
    np.testing.assert_array_equal(np.asarray(st), tok[order])
    np.testing.assert_array_equal(np.asarray(sp), fp[order])
    np.testing.assert_array_equal(np.asarray(xd), x[tok[order]])
    with pytest.raises(ValueError, match="256"):
        moe_dispatch_sort(jnp.asarray(x), jnp.asarray(e), jnp.asarray(p),
                          num_experts=300)


def test_interpret_resolves_from_backend():
    """One backend-derived default: kernels interpret on the CPU and compile
    on a TPU; an explicit flag wins."""
    from repro.kernels import resolve_interpret
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
