"""The unified Model facade: init / loss / prefill / decode over any config.

Responsible for:
* parameter init (real arrays for smoke tests; ``jax.eval_shape`` abstract
  init for the dry-run — full-size models are never materialized on CPU),
* the scan-over-periods traversal (see transformer.py),
* encoder-decoder composition (whisper) and VLM cross-attention stubs,
* cache allocation/threading for serving.

Batch dicts:
  train:   {"tokens": (B,S) i32, "labels": (B,S) i32}         (+ stubs below)
  prefill: {"tokens": (B,S) i32}
  decode:  {"tokens": (B,) i32, "lengths": (B,) i32}
  stubs:   vlm  → {"image_embeds": (B, N_img, D) bf16}
           audio→ {"frames": (B, S_enc, D) bf16}
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import (Params, chunked_softmax_xent, embed, embedding_init,
                     layernorm, layernorm_init, rmsnorm, rmsnorm_init,
                     unembed)
from .transformer import (LayerSpec, layer_apply, layer_cache_shape,
                          layer_decode, layer_init, stage_layout)


def sinusoidal_positions(seq: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2.0 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    moe_strategy: str = "einsum"
    max_decoder_positions: int = 0   # learned decoder positions (whisper)
    # SSM recurrence backend for full-sequence paths: "lax" (associative
    # scan / chunk loop — differentiable, the training default) or "pallas"
    # (single-launch chunked scan, kernels/ssm_scan.py — the serving path).
    scan_impl: str = "lax"

    def __post_init__(self):
        if self.scan_impl not in ("lax", "pallas"):
            raise ValueError(
                f"scan_impl must be 'lax' or 'pallas', got {self.scan_impl!r}")
        self.prefix_specs, self.period_specs, self.repeats = \
            stage_layout(self.cfg)
        self.enc_spec = LayerSpec("attn", False, False, True) \
            if self.cfg.is_encdec else None

    @property
    def recurrent_only(self) -> bool:
        """True when decode state is O(1) per layer (no attention KV grows
        with the sequence) — serving then needs a constant page span per
        request instead of prompt+max_new cache positions."""
        specs = list(self.prefix_specs) + list(self.period_specs)
        return (not self.cfg.is_encdec
                and all(s.kind in ("mamba", "mlstm", "slstm")
                        and not s.has_cross for s in specs))

    # ------------------------------------------------------------------ init
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = iter(jax.random.split(key, 16 + cfg.num_layers
                                   + cfg.encoder_layers))
        params: Params = {
            "embed": embedding_init(next(ks), cfg.padded_vocab, cfg.d_model,
                                    cfg.pdtype()),
        }
        if not cfg.tie_embeddings:
            params["head"] = embedding_init(next(ks), cfg.padded_vocab,
                                            cfg.d_model, cfg.pdtype())
        ninit = layernorm_init if cfg.norm == "layernorm" else rmsnorm_init
        params["final_norm"] = ninit(cfg.d_model, cfg.pdtype())

        if self.prefix_specs:
            params["prefix"] = [layer_init(next(ks), cfg, s)
                                for s in self.prefix_specs]

        def one_period(k):
            kk = jax.random.split(k, len(self.period_specs))
            return [layer_init(kk[i], cfg, s)
                    for i, s in enumerate(self.period_specs)]

        # vmapped over the repeats: the stack is built directly (no second
        # per-layer copy), and under jit the period compiles once instead of
        # unrolling every layer; values equal a per-repeat loop's
        rep_keys = jnp.stack([next(ks) for _ in range(self.repeats)])
        params["stage"] = jax.vmap(one_period)(rep_keys)

        if cfg.is_encdec:
            encs = [layer_init(next(ks), cfg, self.enc_spec)
                    for _ in range(cfg.encoder_layers)]
            params["enc_stage"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                               *encs)
            params["enc_final_norm"] = ninit(cfg.d_model, cfg.pdtype())
            npos = self.max_decoder_positions or 4096
            params["dec_pos"] = (jax.random.normal(
                next(ks), (npos, cfg.d_model), jnp.float32) * 0.01
            ).astype(cfg.pdtype())
        return params

    def abstract_params(self, key=None) -> Any:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)

    # ------------------------------------------------------------- internals
    def _norm(self, p, x):
        f = layernorm if self.cfg.norm == "layernorm" else rmsnorm
        return f(p, x, self.cfg.norm_eps)

    def _encode(self, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
        """Whisper encoder over stub frame embeddings."""
        cfg = self.cfg
        B, S, D = frames.shape
        x = frames.astype(cfg.dtype()) + sinusoidal_positions(S, D, cfg.dtype())
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(carry, lp):
            x = carry
            x, _, _ = layer_apply(cfg, self.enc_spec, lp, x, positions,
                                  causal=False)
            return x, None

        body_fn = jax.checkpoint(body) if cfg.remat == "block" else body
        x, _ = jax.lax.scan(body_fn, x, params["enc_stage"])
        return self._norm(params["enc_final_norm"], x)

    def _stage_scan(self, params: Params, x: jnp.ndarray,
                    positions: jnp.ndarray, *, kv_states, collect_cache: bool,
                    causal: bool = True):
        from ..dist.sharding import constrain, dp
        from jax.sharding import PartitionSpec as P
        cfg = self.cfg
        specs = self.period_specs
        sp_spec = P(dp(), "model", None)   # sequence-parallel residual stream

        def body(carry, stage_lp):
            x, aux = carry
            x = constrain(x, sp_spec)
            payloads = []
            for pos, spec in enumerate(specs):
                x, a, pl = layer_apply(
                    cfg, spec, stage_lp[pos], x, positions, causal=causal,
                    kv_states=kv_states, collect_cache=collect_cache,
                    moe_strategy=self.moe_strategy,
                    scan_impl=self.scan_impl)
                aux = aux + a
                payloads.append(pl)
            x = constrain(x, sp_spec)
            ys = payloads if collect_cache else None
            return (x, aux), ys

        body_fn = jax.checkpoint(body) if cfg.remat == "block" else body
        (x, aux), ys = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                    params["stage"])
        return x, aux, ys

    def _embed_in(self, params, tokens):
        with jax.named_scope("embed"):
            return embed(params["embed"], tokens).astype(self.cfg.dtype())

    def _logits_head(self, params, x):
        cfg = self.cfg
        table = params["embed" if cfg.tie_embeddings else "head"]["table"]
        with jax.named_scope("head"):
            logits = jnp.einsum("...d,vd->...v", x, table).astype(
                jnp.float32)
            if cfg.vocab_padding:
                neg = jnp.full((cfg.vocab_padding,), -1e30, jnp.float32)
                logits = logits.at[..., cfg.vocab_size:].set(neg)
        return logits

    # ----------------------------------------------------------------- train
    def loss_fn(self, params: Params, batch: Dict[str, jnp.ndarray]
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        kv_states = None
        if cfg.family == "vlm":
            kv_states = batch["image_embeds"].astype(cfg.dtype())

        x = self._embed_in(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.is_encdec:
            enc_out = self._encode(params, batch["frames"])
            kv_states = enc_out
            x = x + params["dec_pos"][:S].astype(cfg.dtype())

        for spec, lp in zip(self.prefix_specs, params.get("prefix", [])):
            x, a, _ = layer_apply(cfg, spec, lp, x, positions,
                                  kv_states=kv_states,
                                  moe_strategy=self.moe_strategy,
                                  scan_impl=self.scan_impl)
            aux_total += a

        x, aux, _ = self._stage_scan(params, x, positions,
                                     kv_states=kv_states, collect_cache=False)
        aux_total += aux
        x = self._norm(params["final_norm"], x)

        head = params["embed" if cfg.tie_embeddings else "head"]
        mask = (labels >= 0).astype(jnp.float32)
        labels_safe = jnp.maximum(labels, 0)
        loss = chunked_softmax_xent(head, x, labels_safe,
                                    chunk=cfg.loss_chunk, mask=mask)
        total = loss + 0.01 * aux_total
        return total, {"xent": loss, "aux": aux_total}

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int, *,
                   cross_len: int = 0) -> Any:
        """Zero-filled cache pytree.  Layout mirrors params: 'prefix' list +
        'stage' stacked (R, ...) per period position."""
        cfg = self.cfg

        def alloc(spec: LayerSpec, stacked: bool):
            shapes = layer_cache_shape(cfg, spec, batch, max_seq)
            if spec.has_cross:
                hd = cfg.resolved_head_dim
                shapes["ck"] = ((batch, cross_len, cfg.num_kv_heads, hd),
                                cfg.dtype())
                shapes["cv"] = ((batch, cross_len, cfg.num_kv_heads, hd),
                                cfg.dtype())
            out = {}
            for name, (shape, dt) in shapes.items():
                if stacked:
                    shape = (self.repeats,) + shape
                out[name] = jnp.zeros(shape, dt)
            return out

        cache: Dict[str, Any] = {}
        if self.prefix_specs:
            cache["prefix"] = [alloc(s, False) for s in self.prefix_specs]
        cache["stage"] = [alloc(s, True) for s in self.period_specs]
        return cache

    def abstract_cache(self, batch: int, max_seq: int, *, cross_len: int = 0):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_seq, cross_len=cross_len))

    def prefill(self, params: Params, batch: Dict[str, jnp.ndarray],
                max_seq: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Any]:
        """Full prompt prefill.  Returns (last-token logits (B, V), cache).
        Chunked (by_blocks) prefill lives in repro.serve.prefill."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        max_seq = max_seq or S
        kv_states = None
        cross_payload = None
        if cfg.family == "vlm":
            kv_states = batch["image_embeds"].astype(cfg.dtype())
        x = self._embed_in(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if cfg.is_encdec:
            enc_out = self._encode(params, batch["frames"])
            kv_states = enc_out
            x = x + params["dec_pos"][:S].astype(cfg.dtype())

        prefix_payloads = []
        for spec, lp in zip(self.prefix_specs, params.get("prefix", [])):
            x, _, pl = layer_apply(cfg, spec, lp, x, positions,
                                   kv_states=kv_states, collect_cache=True,
                                   moe_strategy=self.moe_strategy,
                                   scan_impl=self.scan_impl)
            prefix_payloads.append(pl)

        x, _, stage_payloads = self._stage_scan(
            params, x, positions, kv_states=kv_states, collect_cache=True)
        x = self._norm(params["final_norm"], x)
        logits = self._logits_head(params, x[:, -1:])[:, 0]

        cache = self._payloads_to_cache(prefix_payloads, stage_payloads,
                                        B, S, max_seq)
        return logits, cache

    def _payloads_to_cache(self, prefix_payloads, stage_payloads, B, S,
                           max_seq):
        """Place prefill payloads into (possibly larger) cache buffers."""
        cfg = self.cfg

        def place(payload, spec: LayerSpec, stacked: bool):
            out = {}
            for name, arr in payload.items():
                if name in ("k", "v", "latent"):
                    if max_seq != S:
                        # seq axis: stacked → axis 2 else axis 1
                        ax = 2 if stacked else 1
                        shape = list(arr.shape)
                        shape[ax] = max_seq
                        buf = jnp.zeros(tuple(shape), arr.dtype)
                        idx = [slice(None)] * len(shape)
                        idx[ax] = slice(0, S)
                        arr = buf.at[tuple(idx)].set(arr)
                out[name] = arr
            return out

        cache: Dict[str, Any] = {}
        if prefix_payloads:
            cache["prefix"] = [place(pl, s, False) for pl, s in
                               zip(prefix_payloads, self.prefix_specs)]
        cache["stage"] = [place(pl, s, True) for pl, s in
                          zip(stage_payloads, self.period_specs)]
        return cache

    def prefill_chunk(self, params: Params, tokens: jnp.ndarray, cache: Any,
                      pos0, *, all_logits: bool = False
                      ) -> Tuple[jnp.ndarray, Any]:
        """One by_blocks prefill chunk: tokens (B, c) at positions
        [pos0, pos0+c).  Returns (logits, updated cache); logits are the
        last position's (B, V) by default, or the whole chunk's (B, c, V)
        with ``all_logits=True`` — mixed-length batches gather each row's
        last *real* position from these.  ``pos0`` is a traced scalar:
        compilation is keyed on the chunk length only, so the by_blocks
        schedule compiles one program per distinct chunk size."""
        from .transformer import layer_prefill_chunk
        cfg = self.cfg
        B, c = tokens.shape
        x = self._embed_in(params, tokens)
        if cfg.is_encdec:
            dec_pos = jax.lax.dynamic_slice_in_dim(params["dec_pos"],
                                                   pos0, c, 0)
            x = x + dec_pos.astype(cfg.dtype())

        new_cache: Dict[str, Any] = {}
        if self.prefix_specs:
            new_prefix = []
            for spec, lp, lc in zip(self.prefix_specs, params["prefix"],
                                    cache["prefix"]):
                x, lc2 = layer_prefill_chunk(cfg, spec, lp, x, lc, pos0,
                                             moe_strategy=self.moe_strategy,
                                             scan_impl=self.scan_impl)
                new_prefix.append(lc2)
            new_cache["prefix"] = new_prefix

        specs = self.period_specs

        def body(x, xs):
            stage_lp, stage_cache = xs
            new_slices = []
            for pos, spec in enumerate(specs):
                x, c2 = layer_prefill_chunk(
                    cfg, spec, stage_lp[pos], x, stage_cache[pos], pos0,
                    moe_strategy=self.moe_strategy,
                    scan_impl=self.scan_impl)
                new_slices.append(c2)
            return x, new_slices

        x, new_stage = jax.lax.scan(body, x, (params["stage"],
                                              cache["stage"]))
        new_cache["stage"] = new_stage
        x = self._norm(params["final_norm"], x)
        if all_logits:
            logits = self._logits_head(params, x)          # (B, c, V)
        else:
            logits = self._logits_head(params, x[:, -1:])[:, 0]
        return logits, new_cache

    def encode_to_cache(self, params: Params, batch: Dict[str, jnp.ndarray],
                        cache: Any) -> Any:
        """Populate cross-attention K/V (ck/cv) from encoder output / image
        embeddings — run once before chunked prefill of cross-attn models."""
        cfg = self.cfg
        if cfg.family == "vlm":
            kv_states = batch["image_embeds"].astype(cfg.dtype())
        elif cfg.is_encdec:
            kv_states = self._encode(params, batch["frames"])
        else:
            return cache
        hd = cfg.resolved_head_dim
        B, Skv, _ = kv_states.shape

        def fill(lp_cross, lc):
            ck = jnp.einsum("bsd,de->bse", kv_states,
                            lp_cross["wk"]).reshape(B, Skv,
                                                    cfg.num_kv_heads, hd)
            cv = jnp.einsum("bsd,de->bse", kv_states,
                            lp_cross["wv"]).reshape(B, Skv,
                                                    cfg.num_kv_heads, hd)
            lc = dict(lc)
            lc["ck"], lc["cv"] = ck, cv
            return lc

        new_cache = dict(cache)
        if self.prefix_specs:
            new_cache["prefix"] = [
                fill(lp["cross"], lc) if spec.has_cross else lc
                for spec, lp, lc in zip(self.prefix_specs, params["prefix"],
                                        cache["prefix"])]
        new_stage = []
        for pos, spec in enumerate(self.period_specs):
            lc = cache["stage"][pos]
            if spec.has_cross:
                wk = params["stage"][pos]["cross"]["wk"]   # (R, D, KV·hd)
                wv = params["stage"][pos]["cross"]["wv"]
                R = wk.shape[0]
                ck = jnp.einsum("bsd,rde->rbse", kv_states, wk).reshape(
                    R, B, Skv, cfg.num_kv_heads, hd)
                cv = jnp.einsum("bsd,rde->rbse", kv_states, wv).reshape(
                    R, B, Skv, cfg.num_kv_heads, hd)
                lc = dict(lc)
                lc["ck"], lc["cv"] = ck, cv
            new_stage.append(lc)
        new_cache["stage"] = new_stage
        return new_cache

    def decode_step(self, params: Params, tokens: jnp.ndarray, cache: Any,
                    lengths: jnp.ndarray) -> Tuple[jnp.ndarray, Any]:
        """One token per sequence.  tokens: (B,), lengths: (B,) current valid
        prefix length.  Returns (logits (B, vocab), new cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = self._embed_in(params, tokens[:, None])
        positions = lengths
        if cfg.is_encdec:
            x = x + params["dec_pos"][lengths][:, None].astype(cfg.dtype())

        new_cache: Dict[str, Any] = {}
        if self.prefix_specs:
            new_prefix = []
            for spec, lp, lc in zip(self.prefix_specs, params["prefix"],
                                    cache["prefix"]):
                x, lc2 = layer_decode(cfg, spec, lp, x, lc, positions,
                                      lengths, moe_strategy=self.moe_strategy)
                new_prefix.append(lc2)
            new_cache["prefix"] = new_prefix

        specs = self.period_specs

        def body(x, xs):
            stage_lp, stage_cache = xs
            new_slices = []
            for pos, spec in enumerate(specs):
                x, c2 = layer_decode(cfg, spec, stage_lp[pos], x,
                                     stage_cache[pos], positions, lengths,
                                     moe_strategy=self.moe_strategy)
                new_slices.append(c2)
            return x, new_slices

        x, new_stage = jax.lax.scan(body, x, (params["stage"],
                                              cache["stage"]))
        new_cache["stage"] = new_stage
        x = self._norm(params["final_norm"], x)
        logits = self._logits_head(params, x)[:, 0]
        return logits, new_cache


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)


__all__ = ["Model", "build_model", "sinusoidal_positions"]
