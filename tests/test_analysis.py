"""Unit tests for the HLO analyzer and data auditing."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import (HloAnalysis, analyze_hlo,
                                       shape_bytes_and_elems, shape_dims)


def test_shape_parsing():
    b, e = shape_bytes_and_elems("bf16[2,4,8]")
    assert e == 64 and b == 128
    b2, e2 = shape_bytes_and_elems("(f32[4]{0}, s32[2,2]{1,0})")
    assert e2 == 8 and b2 == 32
    assert shape_dims("f32[3,5]{1,0}") == [3, 5]
    assert shape_dims("f32[]") == []


def test_scan_trip_count_multiplied():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    r = analyze_hlo(txt)
    ideal = 8 * 2 * 128 ** 3
    assert 0.95 * ideal < r["flops_per_chip"] < 1.1 * ideal
    # XLA's own counter reports ~1/8 of that (the undercount we fix)
    ca = jax.jit(f).lower(x, w).compile().cost_analysis()
    assert ca["flops"] < 0.2 * r["flops_per_chip"]


def test_dot_flops_single():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    txt = jax.jit(f).lower(a, b).compile().as_text()
    r = analyze_hlo(txt)
    assert abs(r["flops_per_chip"] - 2 * 64 * 256 * 32) / (2*64*256*32) < 0.05


def test_traffic_excludes_elementwise_chains():
    def f(x):
        for _ in range(20):
            x = jnp.tanh(x) + 1.0
        return x
    x = jax.ShapeDtypeStruct((1 << 16,), jnp.float32)
    txt = jax.jit(f).lower(x).compile().as_text()
    r = analyze_hlo(txt)
    # filtered traffic stays near a couple of passes over x, not 20
    assert r["traffic_bytes_per_chip"] <= 12 * (1 << 18)
    assert r["bytes_all_ops_per_chip"] >= r["traffic_bytes_per_chip"]


# ---------------------------------------------------------------------------
# data auditing (repro.data.validate)
# ---------------------------------------------------------------------------

from repro.data.validate import all_finite, audit_pytree, tokens_in_range


def test_all_finite_clean_and_poisoned():
    x = np.ones(100_000, np.float32)
    assert all_finite(x).ok
    x[12345] = np.inf
    r = all_finite(x)
    assert not r.ok
    lo, hi = r.first_bad_block
    assert lo <= 12345 < hi
    assert r.stats.items_run < len(x)          # early abort


def test_tokens_in_range():
    t = np.array([[0, 5, 99], [3, -1, 98]], np.int32)
    assert tokens_in_range(t, 100).ok
    assert not tokens_in_range(t, 50).ok


def test_audit_pytree_flags_bad_leaf():
    tree = {"good": jnp.ones((8, 8)),
            "bad": jnp.array([1.0, float("nan")])}
    ok, bad = audit_pytree(tree)
    assert not ok and any("bad" in p for p in bad)


# ---------------------------------------------------------------------------
# kv cache utilities
# ---------------------------------------------------------------------------

from repro.configs.registry import get_smoke_config
from repro.models.model import Model
from repro.serve.kvcache import PageTable, cache_bytes


def test_cache_bytes_positive_and_scales():
    model = Model(get_smoke_config("llama3-8b"))
    b1 = cache_bytes(model, 2, 64)
    b2 = cache_bytes(model, 2, 128)
    assert 0 < b1 < b2 <= 2 * b1 + 1024


def test_page_table_lifecycle():
    pt = PageTable(page_size=16, num_pages=8)
    pages = pt.allocate(rid=1, seq_len=40)      # 3 pages
    assert len(pages) == 3 and pt.utilization == pytest.approx(3 / 8)
    assert pt.extend(1, 70)                     # grows to 5
    assert len(pt.owner[1]) == 5
    assert pt.allocate(2, 200) is None          # won't fit
    pt.release(1)
    assert pt.utilization == 0.0
