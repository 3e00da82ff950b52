"""The decode step's in-place K/V write against the mask-select it replaced.

``Model.decode_step`` carries the attention layers' stacked K/V through the
layer scan and scatters each lane's new row into the stack.  The reference
here is the step as it was: the stacked cache goes through the scan as xs
and comes back as ys, and every attention layer writes its row with a
mask-select over its whole (B, S, KV, hd) slice.  Both must give the same
bits: logits, every cache leaf, and the tokens of ``make_decode_tick``.
The lanes cover the cases the write has to keep: mixed lengths, a finished
lane that does not advance, and a lane that reaches ``max_seq`` and whose
next write is dropped.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.registry import get_smoke_config
from repro.dist.sharding import mesh_context
from repro.models import transformer
from repro.models.model import Model
from repro.models.transformer import layer_decode
from repro.serve.early_exit import make_decode_tick

S = 16                              # max_seq
LENGTHS = [3, 9, 5, S - 1]          # lane 2 is finished; lane 3 hits S
CROSS_LEN = 4
ARCHS = ["minitron-4b", "jamba-1.5-large-398b", "llama-3.2-vision-11b"]


def _select_write(cache, new, lengths, layer=None):
    """The mask-select write: a lane at ``lengths == S`` matches nothing."""
    assert layer is None
    at = (jnp.arange(cache.shape[1])[None, :] ==
          lengths[:, None])[:, :, None, None]
    return jnp.where(at, new[:, None], cache)


def reference_decode_step(model, params, tokens, cache, lengths):
    """The decode step with the stacked cache as the layer scan's xs/ys."""
    cfg = model.cfg
    x = model._embed_in(params, tokens[:, None])
    new_cache = {}
    if model.prefix_specs:
        new_cache["prefix"] = []
        for spec, lp, lc in zip(model.prefix_specs, params["prefix"],
                                cache["prefix"]):
            x, lc2 = layer_decode(cfg, spec, lp, x, lc, lengths, lengths,
                                  moe_strategy=model.moe_strategy)
            new_cache["prefix"].append(lc2)

    def body(x, xs):
        stage_lp, stage_cache = xs
        new_slices = []
        for pos, spec in enumerate(model.period_specs):
            x, c2 = layer_decode(cfg, spec, stage_lp[pos], x,
                                 stage_cache[pos], lengths, lengths,
                                 moe_strategy=model.moe_strategy)
            new_slices.append(c2)
        return x, new_slices

    x, new_cache["stage"] = jax.lax.scan(body, x, (params["stage"],
                                                   cache["stage"]))
    x = model._norm(params["final_norm"], x)
    return model._logits_head(params, x)[:, 0], new_cache


def _random_cache(model, key):
    """A cache whose every leaf holds noise, so a misplaced write shows."""
    cache = model.init_cache(len(LENGTHS), S, cross_len=CROSS_LEN)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


@functools.lru_cache(maxsize=None)
def _setup(arch):
    model = Model(get_smoke_config(arch))
    params = model.init(jax.random.PRNGKey(0))
    return model, params, _random_cache(model, jax.random.PRNGKey(1))


def _assert_same(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_matches_select_write(arch, monkeypatch):
    model, params, cache0 = _setup(arch)
    lengths = jnp.array(LENGTHS, jnp.int32)
    live = jnp.array([True, True, False, True])
    tokens = jnp.array([5, 11, 17, 23], jnp.int32)
    new, ref = cache0, cache0
    for _ in range(4):
        lg_new, new = jax.jit(model.decode_step)(params, tokens, new, lengths)
        with monkeypatch.context() as m:
            m.setattr(transformer, "kv_write", _select_write)
            lg_ref, ref = jax.jit(functools.partial(
                reference_decode_step, model))(params, tokens, ref, lengths)
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_ref))
        _assert_same(new, ref)
        tokens = jnp.argmax(lg_new[:, :model.cfg.vocab_size],
                            -1).astype(jnp.int32)
        lengths = lengths + live
        live = live & (lengths < S)
    assert int(lengths[3]) == S        # lane 3's last writes were dropped


@pytest.mark.parametrize("arch", ARCHS[:2])
def test_decode_tick_matches_select_write(arch, monkeypatch):
    model, params, cache0 = _setup(arch)
    ref_model = Model(model.cfg)
    ref_model.decode_step = functools.partial(reference_decode_step,
                                              ref_model)
    tokens = jnp.array([5, 11, 17, 23], jnp.int32)
    lengths = jnp.array(LENGTHS, jnp.int32)
    finished = jnp.array([False, False, True, False])
    remaining = jnp.array([6, 6, 6, 1], jnp.int32)
    copy = functools.partial(jax.tree.map, jnp.copy)  # the tick donates
    got = make_decode_tick(model, eos_id=0)(
        params, tokens, copy(cache0), lengths, finished, remaining, 4)
    with monkeypatch.context() as m:
        m.setattr(transformer, "kv_write", _select_write)
        want = make_decode_tick(ref_model, eos_id=0)(
            params, tokens, copy(cache0), lengths, finished, remaining, 4)
    _assert_same(got, want)
    out = np.asarray(got[5])
    assert (out[2] == -1).all()
    assert int(got[2][3]) == S


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("in_mesh", [False, True])
def test_kv_write_matches_select(stacked, in_mesh):
    """Both branches of ``kv_write`` (in place outside a mesh, the
    shard-local select inside one) write what the mask-select writes."""
    B, KV, hd, R = len(LENGTHS), 2, 4, 3
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    stack = jax.random.normal(k1, (R, B, S, KV, hd))
    new = jax.random.normal(k2, (B, KV, hd))
    lengths = jnp.array([0, 7, S - 1, S], jnp.int32)
    layer = jnp.int32(1)
    cache = stack if stacked else stack[1]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    if in_mesh:
        with mesh_context(mesh):
            got = transformer.kv_write(cache, new, lengths,
                                       layer if stacked else None)
    else:
        got = transformer.kv_write(cache, new, lengths,
                                   layer if stacked else None)
    want_slice = _select_write(stack[1], new, lengths)
    want = stack.at[1].set(want_slice) if stacked else want_slice
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
