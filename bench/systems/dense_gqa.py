"""The system under test for a dense GQA decoder: ``repro``'s ``Model``
built from the configuration file, with the reference's weights laid out as
the program holds them (stacked over layers, in the served dtype), made on
the device in one jitted call from the seed."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference.dense_gqa import (make_final_norm, make_layer,
                                       make_table_chunk, seed_key,
                                       table_chunks)


def build_model(model_cfg: Dict):
    from repro.configs.base import ModelConfig
    from repro.models.model import Model
    return Model(ModelConfig(**model_cfg))


def _make(m: Dict, key) -> Dict[str, Any]:
    dt = jnp.dtype(m["param_dtype"])
    n = table_chunks(m["vocab_size"])

    def table(name):
        t = jax.lax.map(lambda c: make_table_chunk(m, key, name, c).astype(dt),
                        jnp.arange(n))
        return {"table": t.reshape(m["vocab_size"], m["d_model"])}

    w = jax.lax.map(
        lambda i: {k: v.astype(dt) for k, v in make_layer(m, key, i).items()},
        jnp.arange(m["num_layers"]))
    layer = {"ln1": {"scale": w["ln1"]},
             "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "ln2": {"scale": w["ln2"]},
             "ffn": {k: w[k] for k in ("up", "up_b", "down", "down_b")}}
    return {"embed": table("embed"), "head": table("head"),
            "final_norm": {"scale": make_final_norm(m, key).astype(dt)},
            "stage": [layer]}


def make_params(model, model_cfg: Dict, seed: int):
    """The program's parameter tree, checked leaf by leaf against the shapes
    and dtypes ``Model.init`` would give."""
    make = jax.jit(lambda key: _make(model_cfg, key))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(make, seed_key(seed))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"program {want}\nbench {got}")
    return make(seed_key(seed))


__all__ = ["build_model", "make_params"]
