"""Model assembly: layer specs → periods → scanned stages → full models.

Architecture heterogeneity (Jamba's 1:7 mamba:attn with alternating MoE,
llama-vision's every-5th cross-attention, xLSTM's 7:1 mLSTM:sLSTM) is handled
by grouping layers into *periods*: the smallest repeating unit of
(mixer-kind, is-moe, has-cross) specs.  Parameters are stacked over period
repeats and the stack is traversed with ``lax.scan`` — one compiled period
body regardless of depth, which is what keeps 72-layer Jamba compilable and
is standard practice at scale (MaxText does the same).

``remat='block'`` wraps the period body in ``jax.checkpoint`` so backward
recomputes activations per period — the baseline activation policy.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (cross_attention, gqa_init, gqa_project_kv,
                        gqa_project_qkv, gqa_self_attention, mla_cache_payload,
                        mla_decode, mla_init, mla_self_attention,
                        blockwise_attention, plain_attention, attn_chunk_sizes,
                        decode_attention)
from .layers import (Params, chunked_softmax_xent, embed, embedding_init,
                     gelu_mlp, gelu_mlp_init, layernorm, layernorm_init,
                     rmsnorm, rmsnorm_init, swiglu, swiglu_init, unembed,
                     dense_init)
from .moe import moe_apply, moe_init
from .ssm import (mamba_forward, mamba_init, mamba_step, mlstm_forward,
                  mlstm_init, mlstm_step, slstm_forward, slstm_init,
                  slstm_step)


# ---------------------------------------------------------------------------
# Layer specs and periods
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str          # attn | mla | mamba | mlstm | slstm
    is_moe: bool
    has_cross: bool
    has_ffn: bool


def layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    specs = []
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if kind == "attn" and cfg.attn_type == "mla":
            kind = "mla"
        has_cross = bool(cfg.cross_attn_period) and \
            (i % cfg.cross_attn_period == cfg.cross_attn_period - 1)
        has_ffn = cfg.d_ff > 0 or (cfg.is_moe and cfg.layer_is_moe(i))
        specs.append(LayerSpec(kind, cfg.layer_is_moe(i), has_cross, has_ffn))
    return specs


def stage_layout(cfg: ModelConfig) -> Tuple[List[LayerSpec], List[LayerSpec], int]:
    """Returns (prefix_specs, period_specs, n_repeats): prefix layers are
    unrolled (deepseek's leading dense layer); the rest is period × repeats."""
    specs = layer_specs(cfg)
    pre = cfg.first_dense_layers
    prefix, rest = specs[:pre], specs[pre:]
    # find the smallest period that tiles `rest`
    for p in range(1, len(rest) + 1):
        if len(rest) % p != 0:
            continue
        if all(rest[i] == rest[i % p] for i in range(len(rest))):
            return prefix, rest[:p], len(rest) // p
    return prefix, rest, 1


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _norm_init(cfg: ModelConfig):
    return (layernorm_init if cfg.norm == "layernorm" else rmsnorm_init)


def _norm(cfg: ModelConfig):
    return (layernorm if cfg.norm == "layernorm" else rmsnorm)


def _ffn_init(key, cfg: ModelConfig, d_ff: int):
    if cfg.ffn_type == "swiglu":
        return swiglu_init(key, cfg.d_model, d_ff, cfg.pdtype())
    return gelu_mlp_init(key, cfg.d_model, d_ff, cfg.pdtype())


def _ffn_apply(cfg: ModelConfig, params, x):
    if cfg.ffn_type == "swiglu":
        return swiglu(params, x)
    if cfg.ffn_type == "relu2":
        h = jnp.einsum("...d,df->...f", x, params["up"]) + params["up_b"]
        h = jnp.square(jax.nn.relu(h))
        return jnp.einsum("...f,fd->...d", h, params["down"]) + params["down_b"]
    return gelu_mlp(params, x)


def layer_init(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    ninit = _norm_init(cfg)
    ks = jax.random.split(key, 6)
    p: Params = {"ln1": ninit(cfg.d_model, cfg.pdtype())}
    if spec.kind == "attn":
        p["mixer"] = gqa_init(ks[0], cfg)
    elif spec.kind == "mla":
        p["mixer"] = mla_init(ks[0], cfg)
    elif spec.kind == "mamba":
        p["mixer"] = mamba_init(ks[0], cfg)
    elif spec.kind == "mlstm":
        p["mixer"] = mlstm_init(ks[0], cfg)
    elif spec.kind == "slstm":
        p["mixer"] = slstm_init(ks[0], cfg)
    else:
        raise ValueError(spec.kind)
    if spec.has_cross:
        p["ln_cross"] = ninit(cfg.d_model, cfg.pdtype())
        p["cross"] = gqa_init(ks[1], cfg, cross=True)
    if spec.has_ffn:
        p["ln2"] = ninit(cfg.d_model, cfg.pdtype())
        if spec.is_moe:
            p["moe"] = moe_init(ks[2], cfg)
        else:
            p["ffn"] = _ffn_init(ks[2], cfg, cfg.dense_ffn_dim)
    return p


# --- full-sequence (train / encoder / prefill) apply ------------------------

def layer_apply(cfg: ModelConfig, spec: LayerSpec, lp: Params, x: jnp.ndarray,
                positions: jnp.ndarray, *, causal: bool = True,
                kv_states: Optional[jnp.ndarray] = None,
                collect_cache: bool = False,
                moe_strategy: str = "einsum",
                scan_impl: str = "lax"
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Returns (x, aux_loss, cache_payload-or-None)."""
    from ..dist.sharding import constrain, dp
    from jax.sharding import PartitionSpec as P
    norm = _norm(cfg)
    aux = jnp.zeros((), jnp.float32)
    payload = None
    h = norm(lp["ln1"], x, cfg.norm_eps)
    if spec.kind == "attn":
        mix = gqa_self_attention(lp["mixer"], cfg, h, positions,
                                 causal=causal)
        if collect_cache:
            k, v = gqa_project_kv(lp["mixer"], cfg, h, positions)
            kv_spec = P(dp(), "model", None, None)
            payload = {"k": constrain(k, kv_spec), "v": constrain(v, kv_spec)}
    elif spec.kind == "mla":
        mix = mla_self_attention(lp["mixer"], cfg, h, positions,
                                 causal=causal)
        if collect_cache:
            latent = mla_cache_payload(lp["mixer"], cfg, h, positions)
            payload = {"latent": constrain(latent, P(dp(), "model", None))}
    elif spec.kind == "mamba":
        mix, st = mamba_forward(lp["mixer"], cfg, h, scan_impl=scan_impl)
        if collect_cache:
            payload = st
    elif spec.kind == "mlstm":
        mix, st = mlstm_forward(lp["mixer"], cfg, h, scan_impl=scan_impl)
        if collect_cache:
            payload = st
    elif spec.kind == "slstm":
        mix, st = slstm_forward(lp["mixer"], cfg, h)
        if collect_cache:
            payload = st
    else:
        raise ValueError(spec.kind)
    x = x + mix

    if spec.has_cross:
        assert kv_states is not None, "cross-attn layer needs kv_states"
        hc = norm(lp["ln_cross"], x, cfg.norm_eps)
        x = x + cross_attention(lp["cross"], cfg, hc, kv_states)
        if collect_cache:
            # store cross K/V so decode never touches the encoder again
            B2, Skv, _ = kv_states.shape
            hd = cfg.resolved_head_dim
            ck = jnp.einsum("bsd,de->bse", kv_states,
                            lp["cross"]["wk"]).reshape(
                B2, Skv, cfg.num_kv_heads, hd)
            cv = jnp.einsum("bsd,de->bse", kv_states,
                            lp["cross"]["wv"]).reshape(
                B2, Skv, cfg.num_kv_heads, hd)
            payload = dict(payload or {})
            payload["ck"] = ck
            payload["cv"] = cv

    if spec.has_ffn:
        h2 = norm(lp["ln2"], x, cfg.norm_eps)
        if spec.is_moe:
            y, aux = moe_apply(lp["moe"], cfg, h2, strategy=moe_strategy)
        else:
            y = _ffn_apply(cfg, lp["ffn"], h2)
        x = x + y
    return x, aux, payload


# --- decode apply ------------------------------------------------------------

def layer_decode(cfg: ModelConfig, spec: LayerSpec, lp: Params,
                 x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                 positions: jnp.ndarray, lengths: jnp.ndarray, *,
                 moe_strategy: str = "einsum"
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B,1,D); cache: per-layer state dict; returns (x, new cache)."""
    norm = _norm(cfg)
    B = x.shape[0]
    h = norm(lp["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if spec.kind == "attn":
        # write the current token's K/V first — it attends to itself.
        # Mask-select (not scatter): a scatter onto the seq-sharded cache
        # makes GSPMD replicate the whole buffer; the select is local per
        # shard and costs the same read/write the attention pass pays anyway.
        with jax.named_scope("attn.qkv"):
            q, k_new, v_new = gqa_project_qkv(lp["mixer"], cfg, h,
                                              positions[:, None])
        with jax.named_scope("attn.kv_write"):
            S_max = cache["k"].shape[1]
            at = (jnp.arange(S_max)[None, :] ==
                  lengths[:, None])[:, :, None, None]      # (B,S,1,1)
            new_cache["k"] = jnp.where(at, k_new[:, 0][:, None], cache["k"])
            new_cache["v"] = jnp.where(at, v_new[:, 0][:, None], cache["v"])
        with jax.named_scope("attn.attend"):
            o = decode_attention(q[:, 0], new_cache["k"], new_cache["v"],
                                 lengths + 1)
        with jax.named_scope("attn.out"):
            y = jnp.einsum("be,ed->bd", o.reshape(B, -1),
                           lp["mixer"]["wo"])[:, None]
    elif spec.kind == "mla":
        y, new_latent = mla_decode(lp["mixer"], cfg, h, cache["latent"],
                                   positions, lengths)
        new_cache["latent"] = new_latent
    elif spec.kind == "mamba":
        y, st = mamba_step(lp["mixer"], cfg, h, cache)
        new_cache.update(st)
    elif spec.kind == "mlstm":
        y, st = mlstm_step(lp["mixer"], cfg, h, cache)
        new_cache.update(st)
    elif spec.kind == "slstm":
        y, st = slstm_step(lp["mixer"], cfg, h, cache)
        new_cache.update(st)
    else:
        raise ValueError(spec.kind)
    x = x + y

    if spec.has_cross:
        hc = norm(lp["ln_cross"], x, cfg.norm_eps)
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,de->bse", hc, lp["cross"]["wq"]).reshape(
            B, cfg.num_heads, hd)
        kvlen = jnp.full((B,), cache["ck"].shape[1], jnp.int32)
        o = decode_attention(q, cache["ck"], cache["cv"], kvlen)
        x = x + jnp.einsum("be,ed->bd", o.reshape(B, -1),
                           lp["cross"]["wo"])[:, None]

    if spec.has_ffn:
        h2 = norm(lp["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if spec.is_moe:
                y, _ = moe_apply(lp["moe"], cfg, h2, strategy=moe_strategy,
                                 group_size=min(256, x.shape[0]))
            else:
                y = _ffn_apply(cfg, lp["ffn"], h2)
        x = x + y
    return x, new_cache


# --- chunked-prefill apply (by_blocks serving path) --------------------------

def layer_prefill_chunk(cfg: ModelConfig, spec: LayerSpec, lp: Params,
                        x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                        pos0, *, moe_strategy: str = "einsum",
                        scan_impl: str = "lax"
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Process chunk positions [pos0, pos0+c) against cached history.

    x: (B, c, D).  Attention sees cache[:pos0] + intra-chunk causal; new KV
    is written into the cache.  SSM states continue from the cache.  ``pos0``
    is a *traced* scalar: one compilation per distinct chunk length ``c``,
    reused at every position (the by_blocks schedule then compiles O(log S)
    programs total, not O(log²S)).  The price is that attention runs over the
    full cache width with the causal mask doing the windowing — positions
    beyond pos0+c are masked to exactly zero probability, so the result is
    bit-equal to the sliced-history form.
    """
    norm = _norm(cfg)
    B, c, D = x.shape
    new_cache = dict(cache)
    h = norm(lp["ln1"], x, cfg.norm_eps)
    positions = pos0 + jnp.broadcast_to(jnp.arange(c), (B, c))

    if spec.kind == "attn":
        with jax.named_scope("attn.qkv"):
            q, k, v = gqa_project_qkv(lp["mixer"], cfg, h, positions)
        with jax.named_scope("attn.kv_write"):
            new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos0,
                                                        1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos0,
                                                        1)
        new_cache["k"], new_cache["v"] = new_k, new_v
        S_max = new_k.shape[1]
        qc, kc = attn_chunk_sizes(c, S_max)
        with jax.named_scope("attn.attend"):
            if c <= 256 and S_max <= 1024:
                o = plain_attention(q, new_k, new_v, causal=True,
                                    q_offset=pos0)
            else:
                o = blockwise_attention(q, new_k, new_v, causal=True,
                                        q_chunk=qc, kv_chunk=kc,
                                        q_offset=pos0)
        with jax.named_scope("attn.out"):
            y = jnp.einsum("bse,ed->bsd", o.reshape(B, c, -1),
                           lp["mixer"]["wo"])
    elif spec.kind == "mla":
        # absorbed chunk attention against the latent history
        payload = mla_cache_payload(lp["mixer"], cfg, h, positions)
        new_lat = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], payload, pos0, 1)
        new_cache["latent"] = new_lat
        y = _mla_chunk_absorbed(lp["mixer"], cfg, h, new_lat, positions,
                                pos0, c)
    elif spec.kind == "mamba":
        from .ssm import mamba_forward as _mf
        y, st = _mf(lp["mixer"], cfg, h, h0=cache["ssm"],
                    conv_buf=cache["conv"], scan_impl=scan_impl)
        new_cache.update(st)
    elif spec.kind == "mlstm":
        from .ssm import mlstm_forward
        y, st = mlstm_forward(lp["mixer"], cfg, h, state=cache,
                              scan_impl=scan_impl)
        new_cache.update({k2: st[k2] for k2 in ("C", "n", "m", "conv")})
    elif spec.kind == "slstm":
        from .ssm import slstm_forward
        y, st = slstm_forward(lp["mixer"], cfg, h, state=cache)
        new_cache.update({k2: st[k2] for k2 in ("c", "n", "h", "m", "conv")})
    else:
        raise ValueError(spec.kind)
    x = x + y

    if spec.has_cross:
        hc = norm(lp["ln_cross"], x, cfg.norm_eps)
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,de->bse", hc, lp["cross"]["wq"]).reshape(
            B, c, cfg.num_heads, hd)
        o = plain_attention(q, cache["ck"], cache["cv"], causal=False)
        x = x + jnp.einsum("bse,ed->bsd", o.reshape(B, c, -1),
                           lp["cross"]["wo"])

    if spec.has_ffn:
        h2 = norm(lp["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if spec.is_moe:
                y2, _ = moe_apply(lp["moe"], cfg, h2, strategy=moe_strategy,
                                  group_size=min(256, c))
            else:
                y2 = _ffn_apply(cfg, lp["ffn"], h2)
        x = x + y2
    return x, new_cache


def _mla_chunk_absorbed(params: Params, cfg: ModelConfig, h: jnp.ndarray,
                        latent: jnp.ndarray, positions: jnp.ndarray,
                        pos0, c: int) -> jnp.ndarray:
    """MLA chunk attention in absorbed form (latent-history scoring).

    ``pos0`` may be traced — scoring runs over the full latent buffer and the
    causal mask (exact −inf → exactly-zero softmax weight) does the history
    windowing, so compilation is keyed on the chunk length only."""
    from .attention import NEG_INF
    from .layers import apply_rope, rope_table
    B = h.shape[0]
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    S_hist = latent.shape[1]
    scale = 1.0 / math.sqrt(nd + rd)

    q = jnp.einsum("bsd,de->bse", h, params["wq"]).reshape(B, c, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    cos, sin = rope_table(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    w_uk = params["wkv_up"].reshape(r, H, nd + vd)[..., :nd]
    q_abs = jnp.einsum("bchn,rhn->bchr", q_nope, w_uk)

    c_hist, rope_hist = latent[..., :r], latent[..., r:]
    logits = (jnp.einsum("bchr,bsr->bhcs", q_abs, c_hist,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bchr,bsr->bhcs", q_rope, rope_hist,
                           preferred_element_type=jnp.float32)) * scale
    q_pos = pos0 + jnp.arange(c)
    k_pos = jnp.arange(S_hist)
    mask = q_pos[:, None] >= k_pos[None, :]
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhcs,bsr->bchr", p.astype(c_hist.dtype), c_hist,
                       preferred_element_type=jnp.float32)
    w_uv = params["wkv_up"].reshape(r, H, nd + vd)[..., nd:]
    o = jnp.einsum("bchr,rhv->bchv", o_lat.astype(h.dtype), w_uv)
    return jnp.einsum("bce,ed->bcd", o.reshape(B, c, H * vd), params["wo"])


# ---------------------------------------------------------------------------
# cache allocation
# ---------------------------------------------------------------------------

def layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      max_seq: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Returns {name: (shape, dtype)} for one layer's decode state."""
    dt = cfg.dtype()
    d = cfg.d_model
    di = cfg.ssm_expand * d
    if spec.kind == "attn":
        hd = cfg.resolved_head_dim
        kv = cfg.num_kv_heads
        return {"k": ((batch, max_seq, kv, hd), dt),
                "v": ((batch, max_seq, kv, hd), dt)}
    if spec.kind == "mla":
        payload = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return {"latent": ((batch, max_seq, payload), dt)}
    if spec.kind == "mamba":
        return {"ssm": ((batch, di, cfg.ssm_state_dim), jnp.float32),
                "conv": ((batch, cfg.ssm_conv_dim - 1, di), dt)}
    if spec.kind == "mlstm":
        H = cfg.num_heads
        dh = di // H
        return {"C": ((batch, H, dh, dh), jnp.float32),
                "n": ((batch, H, dh), jnp.float32),
                "m": ((batch, H), jnp.float32),
                "conv": ((batch, cfg.ssm_conv_dim - 1, di), dt)}
    if spec.kind == "slstm":
        return {k: ((batch, d), jnp.float32) for k in ("c", "n", "h", "m")} | \
            {"conv": ((batch, cfg.ssm_conv_dim - 1, d), dt)}
    raise ValueError(spec.kind)


__all__ = [
    "LayerSpec", "layer_specs", "stage_layout", "layer_init", "layer_apply",
    "layer_decode", "layer_cache_shape",
]
