"""Sharding-rule tests on small host meshes (the dry-run covers 512)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.registry import get_config, get_smoke_config
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 mesh_context, moments_shardings,
                                 param_pspec, params_shardings,
                                 sanitize_spec, zero1_spec)
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model

# Mesh-materializing tests need ≥4 real host devices.  Run them with
#   XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest tests/test_sharding.py
# (the default suite sees 1 device by design — dry-run owns the 512 flag).
needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs XLA_FLAGS device_count>=4")


def mesh2x2():
    return make_host_mesh(2, 2)


def test_param_pspec_rules():
    cfg = get_config("llama3-8b")
    assert param_pspec(cfg, "embed/table", 2) == P("model", None)
    assert param_pspec(cfg, "stage/0/mixer/wq", 3) == P(None, None, "model")
    assert param_pspec(cfg, "stage/0/mixer/wo", 3) == P(None, "model", None)
    assert param_pspec(cfg, "stage/0/ffn/gate", 3) == P(None, None, "model")
    assert param_pspec(cfg, "stage/0/ffn/down", 3) == P(None, "model", None)


def test_param_pspec_moe_2d():
    cfg = get_config("jamba-1.5-large-398b")
    assert param_pspec(cfg, "stage/0/moe/gate", 4) == \
        P(None, "model", None, "data")
    assert param_pspec(cfg, "stage/0/moe/down", 4) == \
        P(None, "model", "data", None)


from conftest import ShapeOnlyMesh  # sanitize/zero1 only read axis sizes


def test_sanitize_drops_nondividing():
    mesh = ShapeOnlyMesh(data=2, model=2)
    s = sanitize_spec(mesh, P("model", None), (3, 8))
    assert s == P(None, None)
    s2 = sanitize_spec(mesh, P("model", "data"), (4, 6))
    assert s2 == P("model", "data")


def test_zero1_adds_data_axis():
    mesh = ShapeOnlyMesh(data=2, model=2)
    s = zero1_spec(mesh, P(None, "model"), (8, 4))
    assert s == P("data", "model")
    # already data-sharded → unchanged
    s2 = zero1_spec(mesh, P("data", "model"), (8, 4))
    assert s2 == P("data", "model")


@needs_mesh
def test_params_shardings_cover_tree():
    cfg = get_smoke_config("jamba-1.5-large-398b")
    model = Model(cfg)
    aparams = model.abstract_params()
    mesh = mesh2x2()
    sh = params_shardings(cfg, aparams, mesh)
    n_leaves = len(jax.tree.leaves(aparams))
    assert len(jax.tree.leaves(sh)) == n_leaves
    ms = moments_shardings(cfg, aparams, mesh)
    assert len(jax.tree.leaves(ms)) == n_leaves


@needs_mesh
def test_cache_shardings_layouts():
    cfg = get_smoke_config("llama3-8b")
    model = Model(cfg)
    mesh = mesh2x2()
    acache = model.abstract_cache(batch=4, max_seq=32)
    sh = cache_shardings(cfg, mesh, acache, batch=4)
    k_shard = sh["stage"][0]["k"]
    # (R, B, S, KV, hd): batch over data, seq over model
    assert k_shard.spec == P(None, "data", "model", None, None)
    # batch=1 (long-context): seq takes every axis
    acache1 = model.abstract_cache(batch=1, max_seq=32)
    sh1 = cache_shardings(cfg, mesh, acache1, batch=1)
    assert sh1["stage"][0]["k"].spec == P(None, None, ("data", "model"),
                                          None, None)


@needs_mesh
def test_sharded_train_equals_unsharded():
    """Numerical equivalence: the same train step, sharded vs single-device."""
    import dataclasses
    from repro.optim.adamw import AdamWConfig, init_state
    from repro.train.step import TrainState, make_train_step

    cfg = dataclasses.replace(get_smoke_config("llama3-8b"),
                              param_dtype="float32",
                              compute_dtype="float32")
    model = Model(cfg)
    opt_cfg = AdamWConfig(lr_peak=1e-3, warmup_steps=1)
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params=params, opt=init_state(opt_cfg, params))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 1,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                     cfg.vocab_size),
    }
    step = make_train_step(model, opt_cfg, num_microbatches=2)
    ref_state, ref_metrics = jax.jit(step)(state, batch)

    mesh = mesh2x2()
    with mesh_context(mesh):
        sh_state, sh_metrics = jax.jit(step)(state, batch)
    assert abs(float(ref_metrics["loss"]) - float(sh_metrics["loss"])) < 1e-4
    # fp32 reduction order differs under sharded psums; 5e-5 abs is the
    # observed single-element drift ceiling on the 2x2 host mesh
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(sh_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=2e-4)


@pytest.mark.parametrize("shape", [dict(data=1, model=1),
                                   dict(data=1, model=1, pod=1)])
def test_launch_meshes_scatter_under_mesh_context(shape):
    """Launch meshes carry Auto axes: a batch dim constrained over 'data'
    then scattered (the MoE combine's pattern) resolves its sharding under
    ``mesh_context`` instead of raising ``ShardingTypeError``."""
    from jax.sharding import AxisType
    from repro.dist.sharding import constrain

    mesh = make_host_mesh(**shape)
    assert set(mesh.axis_types) == {AxisType.Auto}

    def combine(y, idx):
        y = constrain(y, P("data", None))
        return jnp.zeros((8, 4), y.dtype).at[idx].add(y)

    y = jnp.arange(64.0).reshape(16, 4)
    idx = jnp.arange(16) % 8
    with mesh_context(mesh):
        out = jax.jit(combine)(y, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(combine(y, idx)))
