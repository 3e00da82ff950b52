"""Plain float32 reference of a dense decoder with grouped-query attention.

The block, as the configuration file describes it: RMSNorm, GQA attention
with rotate-half rotary embeddings over the whole head, a squared-ReLU MLP
with biases, untied embedding and output head.  Written from those
equations in ``jax.numpy`` with every matrix product at ``highest``
precision; it imports nothing of the system under test.

The weights are made here too, from the run's seed, and the system under
test is handed the same values (``bench/systems/dense_gqa.py``): every
entry is a whole number of 16 bits times a power of two, so it is exact in
float32 whatever the compiler fuses, and rounds to the served dtype the same
way on both sides.  A big table is made in row chunks and a layer alone, so
that the reference can remake any part of the model without holding it all.

``precision="fp8"`` is the control: the same forward with the inputs of every
matrix product rounded to float8 e4m3 (scaled per row and per output
column), the lower precision a later change might be tempted to serve in.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYER_TENSORS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "up", "up_b", "down",
                 "down_b")
TOP_TENSORS = ("embed", "head", "final_norm")
CHUNK_ROWS_MAX = 16384
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits (fold in both halves)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _pow2(x: float) -> float:
    return 2.0 ** round(math.log2(x))


def _uniform(key, shape, half_width: float) -> jnp.ndarray:
    """Uniform on a 16-bit grid of [-half_width, half_width); exact in f32."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    k = (bits >> 16).astype(jnp.int32) - 32768
    return k.astype(jnp.float32) * (half_width / 32768.0)


def dims(m: Dict) -> Dict[str, int]:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return dict(d=m["d_model"], H=m["num_heads"], KV=m["num_kv_heads"],
                hd=hd, ff=m["d_ff"], V=m["vocab_size"], L=m["num_layers"])


def layer_shapes(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], float, float]]:
    """name -> (shape, centre, half width) of one layer's tensors."""
    z = dims(m)
    d, q, kv, ff = z["d"], z["H"] * z["hd"], z["KV"] * z["hd"], z["ff"]
    return {
        "ln1": ((d,), 1.0, 2.0 ** -4),
        "wq": ((d, q), 0.0, _pow2(math.sqrt(3.0 / d))),
        "wk": ((d, kv), 0.0, _pow2(math.sqrt(3.0 / d))),
        "wv": ((d, kv), 0.0, _pow2(math.sqrt(3.0 / d))),
        "wo": ((q, d), 0.0, _pow2(math.sqrt(3.0 / q))),
        "ln2": ((d,), 1.0, 2.0 ** -4),
        "up": ((d, ff), 0.0, _pow2(math.sqrt(3.0 / d))),
        "up_b": ((ff,), 0.0, 2.0 ** -6),
        "down": ((ff, d), 0.0, _pow2(math.sqrt(3.0 / ff))),
        "down_b": ((d,), 0.0, 2.0 ** -6),
    }


def table_chunks(vocab: int) -> int:
    """Fewest chunks of at most CHUNK_ROWS_MAX rows that divide the vocab."""
    n = max(1, -(-vocab // CHUNK_ROWS_MAX))
    while vocab % n:
        n += 1
    return n


def _tensor_key(key, layer: int | jnp.ndarray, name: str):
    names = LAYER_TENSORS + TOP_TENSORS
    return jax.random.fold_in(jax.random.fold_in(key, layer),
                              names.index(name))


def make_layer(m: Dict, key, layer) -> Dict[str, jnp.ndarray]:
    """One layer's weights in f32, already rounded to the served dtype.
    ``layer`` may be traced."""
    dt = jnp.dtype(m["param_dtype"])
    out = {}
    for name, (shape, centre, hw) in layer_shapes(m).items():
        w = centre + _uniform(_tensor_key(key, layer + 1, name), shape, hw)
        out[name] = w.astype(dt).astype(jnp.float32)
    return out


def make_table_chunk(m: Dict, key, name: str, chunk) -> jnp.ndarray:
    """Rows [chunk*rows, (chunk+1)*rows) of the embedding or head table."""
    z = dims(m)
    rows = z["V"] // table_chunks(z["V"])
    k = jax.random.fold_in(_tensor_key(key, 0, name), chunk)
    w = _uniform(k, (rows, z["d"]), 2.0 ** -5)
    return w.astype(jnp.dtype(m["param_dtype"])).astype(jnp.float32)


def make_final_norm(m: Dict, key) -> jnp.ndarray:
    d = dims(m)["d"]
    w = 1.0 + _uniform(_tensor_key(key, 0, "final_norm"), (d,), 2.0 ** -4)
    return w.astype(jnp.dtype(m["param_dtype"])).astype(jnp.float32)


# ---------------------------------------------------------------- forward

def _q8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(a: jnp.ndarray, w: jnp.ndarray, fp8: bool) -> jnp.ndarray:
    """a (..., k) @ w (k, n) in f32 at highest precision."""
    if fp8:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Rotate-half rotary embedding over the whole head: x (S, h, hd)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attention(m, w, h, fp8):
    """Causal GQA self-attention of one sequence h (S, d)."""
    z = dims(m)
    S = h.shape[0]
    H, KV, hd = z["H"], z["KV"], z["hd"]
    pos = jnp.arange(S)
    q = _mm(h, w["wq"], fp8).reshape(S, H, hd)
    k = _mm(h, w["wk"], fp8).reshape(S, KV, hd)
    v = _mm(h, w["wv"], fp8).reshape(S, KV, hd)
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)          # head h reads kv head h // G
    v = jnp.repeat(v, H // KV, axis=1)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qhe,khe->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if fp8:
        p = _q8(p, -1)
    o = jnp.einsum("hqk,khe->qhe", p, v, precision=jax.lax.Precision.HIGHEST)
    return _mm(o.reshape(S, H * hd), w["wo"], fp8)


def _layer(m, w, x, fp8: bool):
    """x (n, S, d) -> (n, S, d): one pre-norm block, sequence by sequence."""
    eps = m["norm_eps"]

    def one(xs):
        h = _rms(xs, w["ln1"], eps)
        xs = xs + _attention(m, w, h, fp8)
        h = _rms(xs, w["ln2"], eps)
        u = jnp.square(jax.nn.relu(_mm(h, w["up"], fp8) + w["up_b"]))
        return xs + _mm(u, w["down"], fp8) + w["down_b"]

    return jax.lax.map(one, x)


class Reference:
    """The reference for one configuration and seed.  ``logit_gaps`` runs it
    over sequences and reads, at the positions given, how far a given token's
    logit lies below the reference's best (and, for the control, how far the
    control's own best token lies below it)."""

    def __init__(self, model_cfg: Dict, seed: int):
        self.m = dict(model_cfg)
        self.key = seed_key(seed)
        m = self.m
        self._layer_w = jax.jit(lambda key, i: make_layer(m, key, i))
        self._chunk = jax.jit(
            lambda key, name, c: make_table_chunk(m, key, name, c),
            static_argnums=1)
        self._layer_fn = {
            p: jax.jit(lambda w, x, _p=p: _layer(m, w, x, _p == "fp8"))
            for p in ("f32", "fp8")}

        @jax.jit
        def take(out, tab, flat, c):
            local = flat - c * tab.shape[0]
            inside = (local >= 0) & (local < tab.shape[0])
            rows = tab[jnp.clip(local, 0, tab.shape[0] - 1)]
            return jnp.where(inside[:, None], rows, out)

        self._take = take

    def _embed(self, tokens: np.ndarray) -> jnp.ndarray:
        """(n, S) ids -> (n, S, d) f32 rows, one table chunk at a time."""
        z = dims(self.m)
        flat = jnp.asarray(tokens.reshape(-1))
        out = jnp.zeros((flat.size, z["d"]), jnp.float32)
        for c in range(table_chunks(z["V"])):
            out = self._take(out, self._chunk(self.key, "embed", c), flat, c)
        return out.reshape(tokens.shape + (z["d"],))

    def hidden(self, tokens: np.ndarray, precision: str) -> jnp.ndarray:
        """Final-normed hidden states (n, S, d) of padded sequences."""
        x = self._embed(tokens)
        fn = self._layer_fn[precision]
        for i in range(self.m["num_layers"]):
            x = fn(self._layer_w(self.key, i), x)
        fn_norm = make_final_norm(self.m, self.key)
        return _rms(x, fn_norm, self.m["norm_eps"])

    def logit_gaps(self, seqs: Sequence[np.ndarray],
                   positions: Sequence[np.ndarray],
                   tokens: Sequence[np.ndarray], *, control: bool = False,
                   pad_to: int = 256) -> Dict[str, np.ndarray]:
        """For sequence i, at each position p of ``positions[i]``: the gap
        ``max(ref) - ref[tokens[i][j]]`` (``gap``) and, with ``control``,
        the gap of the token the fp8 forward puts first (``control_gap``).
        Lengths are padded to ``pad_to`` so that few shapes compile."""
        n = len(seqs)
        S = -(-max(len(s) for s in seqs) // pad_to) * pad_to
        toks = np.zeros((n, S), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        rows = np.concatenate([np.full(len(p), i) for i, p in
                               enumerate(positions)])
        cols = np.concatenate(list(positions))
        want = np.concatenate(list(tokens)).astype(np.int32)
        P = len(want)
        Pp = -(-P // pad_to) * pad_to
        pad = lambda a: np.concatenate([a, np.zeros(Pp - P, a.dtype)])
        rows, cols, want = pad(rows), pad(cols), pad(want)
        with jax.default_matmul_precision("highest"):
            h = _pick(self.hidden(toks, "f32"), rows, cols)
            hc = (_pick(self.hidden(toks, "fp8"), rows, cols)
                  if control else None)
            out = self._gaps(h, hc, jnp.asarray(want))
        return {k: v[:P] for k, v in out.items()}

    def _gaps(self, h, hc, want) -> Dict[str, np.ndarray]:
        nchunk = table_chunks(dims(self.m)["V"])
        P = h.shape[0]
        st = (jnp.full((P,), -jnp.inf), jnp.zeros((P,)),
              jnp.full((P,), -jnp.inf), jnp.zeros((P,)))
        for c in range(nchunk):
            tab = self._chunk(self.key, "head", c)          # (rows, d)
            st = _head_chunk(h, hc, tab, want, c, st)
        best, at_want, _, c_ref = st
        out = {"gap": np.asarray(best - at_want)}
        if hc is not None:
            out["control_gap"] = np.asarray(best - c_ref)
        return out


@jax.jit
def _pick(x, rows, cols):
    return x[rows, cols]


@jax.jit
def _head_chunk(h, hc, tab, want, c, st):
    """Fold one chunk of the head's rows into the running readings: the
    reference's best logit, its logit at ``want``, and the control's best
    logit with the reference's logit at that token."""
    best, at_want, c_best, c_ref = st
    rows = tab.shape[0]
    hi = jax.lax.Precision.HIGHEST
    lg = jnp.matmul(h, tab.T, precision=hi)
    best = jnp.maximum(best, lg.max(-1))
    local = want - c * rows
    inside = (local >= 0) & (local < rows)
    got = jnp.take_along_axis(lg, jnp.clip(local, 0, rows - 1)[:, None],
                              -1)[:, 0]
    at_want = jnp.where(inside, got, at_want)
    if hc is not None:
        lc = jnp.matmul(_q8(hc, -1), _q8(tab, -1).T, precision=hi)
        arg = lc.argmax(-1)
        top = jnp.take_along_axis(lc, arg[:, None], -1)[:, 0]
        ref_at = jnp.take_along_axis(lg, arg[:, None], -1)[:, 0]
        better = top > c_best
        c_best = jnp.where(better, top, c_best)
        c_ref = jnp.where(better, ref_at, c_ref)
    return best, at_want, c_best, c_ref


__all__ = ["Reference", "make_layer", "make_table_chunk", "make_final_norm",
           "seed_key", "table_chunks", "layer_shapes", "dims"]
