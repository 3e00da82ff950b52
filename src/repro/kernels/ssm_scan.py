"""Chunked associative scans for the SSM recurrences (ROADMAP item 4).

A linear recurrence ``h_t = a_t · h_{t-1} + b_t`` is the composition of
affine maps, and affine maps form a monoid::

    (a1, b1) ∘ (a2, b2) = (a1·a2,  b2 + a2·b1)      unit (1, 0)

so the whole recurrence is ONE associative scan — the paper's
"sequence of parallel operations" shape.  On a launch-per-node tree that
scan costs ``log n`` launches; here it reuses the ``tile_scan`` carry
pattern (a block-local fold + a cross-block carry pytree in VMEM scratch,
the same machinery ``histogram_offsets`` uses), so the launch count is 1
regardless of sequence length.  Equivalence guarantee: for any
monoid the output equals ``jax.lax.associative_scan(combine, xs)`` seeded
with ``carry0`` — pinned by tests/test_ssm_scan.py and the
``BENCH_scan_ssm.json`` equivalence rows.

Two monoids ship here (see src/repro/models/DESIGN.md for derivations):

* ``affine_combine`` — Mamba's selective scan.  Elements are the
  discretized pairs ``(dA_t, dBx_t)``; seeding the carry with
  ``(1, h0)`` makes the scanned second component *be* the hidden states.
  Strictly elementwise, so ``batched_scan`` tiles the (Di·N) feature axis.
* ``logspace_affine_combine`` — the mLSTM chunk carry.  Elements
  ``(la, m, Ĉ, n̂)`` represent the stabilized affine map
  ``X ↦ exp(la)·X + exp(m)·(Ĉ, n̂)`` on the matrix memory; the combine
  max-rebases ``m`` so nothing ever overflows (unit uses ``LOG_ZERO``,
  not −inf: ``-inf − -inf = nan`` inside ``exp`` would poison the unit).
  Matrix leaves with different shapes → ``tree_scan``, one scan per head,
  the ``(dh, dh)`` memory tiled by rows.

The public wrappers are jit-cached on shape so the serving hot loop never
retraces; ``*_ref`` twins (pure ``lax.scan`` / ``lax.associative_scan``)
are the benchmark baselines and the test oracles.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import resolve_interpret
from .tile_scan import batched_scan, tree_scan

LOG_ZERO = -1e30   # the repo-wide "log of zero" that survives exp/arith
# rows of the (dh, dh) mLSTM memory per grid step: with 8 chunks per block a
# step holds 2 MiB of it at dh=1024, well inside the 16 MiB VMEM limit
MLSTM_ROW_BLOCK = 64


# ---------------------------------------------------------------------------
# monoids
# ---------------------------------------------------------------------------

def affine_combine(a: Tuple[jnp.ndarray, jnp.ndarray],
                   b: Tuple[jnp.ndarray, jnp.ndarray]):
    """(gain, offset) pair monoid of ``h ↦ gain·h + offset`` maps."""
    a1, b1 = a
    a2, b2 = b
    return (a1 * a2, b2 + a2 * b1)


AFFINE_UNITS = (1.0, 0.0)


def _scale(s: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``s`` broadcast over the trailing feature axes of ``x``."""
    return s.reshape(s.shape + (1,) * (x.ndim - s.ndim)) * x


def logspace_affine_combine(a, b):
    """Stabilized log-space affine monoid for the mLSTM matrix memory.

    Elements ``(la, m, C, n)`` denote ``X ↦ exp(la)·X + exp(m)·(C, n)``
    with ``(C, n)`` stored at scale ``exp(m)`` — i.e. the true update is
    ``exp(m)·C``.  The combine rebases both terms onto
    ``m' = max(m1 + la2, m2)``, so every exponent is ≤ 0: no overflow for
    any gate magnitudes.  ``la`` never enters an exp by itself.  The scales
    broadcast over the trailing axes of ``C``/``n``, so the same combine
    takes the model's ``(B, H)`` heads and the kernel's per-head ``(1, 1)``
    rows.
    """
    la1, m1, C1, n1 = a
    la2, m2, C2, n2 = b
    m = jnp.maximum(m1 + la2, m2)
    s1 = jnp.exp(m1 + la2 - m)
    s2 = jnp.exp(m2 - m)
    return (la1 + la2, m, _scale(s1, C1) + _scale(s2, C2),
            _scale(s1, n1) + _scale(s2, n2))


LOGSPACE_UNITS = (0.0, LOG_ZERO, 0.0, 0.0)


# ---------------------------------------------------------------------------
# jit-cached fixed-shape entry points
# ---------------------------------------------------------------------------

_JITS: Dict[Any, Callable] = {}


def _cached(key, build) -> Callable:
    fn = _JITS.get(key)
    if fn is None:
        fn = _JITS[key] = jax.jit(build())
    return fn


def mamba_assoc_scan(dA: jnp.ndarray, dBx: jnp.ndarray, h0: jnp.ndarray, *,
                     block: int = 64, fblock: int = 2048,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Chunked selective scan: ``h_t = dA_t · h_{t-1} + dBx_t`` over axis 1.

    dA, dBx: (B, c, Di, N) fp32;  h0: (B, Di, N) → states (B, c, Di, N),
    ONE pallas launch for any ``c``.
    """
    interpret = resolve_interpret(interpret)
    key = ("mamba", dA.shape, str(dA.dtype), block, fblock, interpret)

    def build():
        def run(dA, dBx, h0):
            _, states = batched_scan(
                (dA, dBx), combine=affine_combine, units=AFFINE_UNITS,
                carry0=(jnp.ones_like(h0), h0), inclusive=True,
                block=block, fblock=fblock, interpret=interpret,
                kind="ssm_scan")
            return states
        return run

    return _cached(key, build)(dA, dBx, h0)


def mamba_assoc_scan_ref(dA: jnp.ndarray, dBx: jnp.ndarray,
                         h0: jnp.ndarray) -> jnp.ndarray:
    """lax.associative_scan oracle (the pre-Pallas model path)."""
    prefA, within = jax.lax.associative_scan(affine_combine, (dA, dBx),
                                             axis=1)
    return within + prefA * h0[:, None]


def mamba_seq_scan_ref(dA: jnp.ndarray, dBx: jnp.ndarray,
                       h0: jnp.ndarray) -> jnp.ndarray:
    """Honest per-step lax.scan — the launch-per-step benchmark baseline."""
    def body(h, ab):
        a, b = ab
        h2 = a * h + b
        return h2, h2

    _, states = jax.lax.scan(
        body, h0, (dA.transpose(1, 0, 2, 3), dBx.transpose(1, 0, 2, 3)))
    return states.transpose(1, 0, 2, 3)


def mlstm_carry_scan(la: jnp.ndarray, mS: jnp.ndarray, Chat: jnp.ndarray,
                     nhat: jnp.ndarray, carry0, *, block: int = 8,
                     interpret: Optional[bool] = None):
    """Exclusive monoid scan over the chunk axis → state ENTERING each chunk.

    la, mS: (nc, B, H);  Chat: (nc, B, H, dh, dh);  nhat: (nc, B, H, dh) —
    per-chunk summaries.  ``carry0 = (m0, C0, n0)`` is the state entering
    chunk 0.  Returns (la_ent, m_ent, C_ent, n_ent) with
    ``ent[k] = carry0 ∘ e_0 ∘ … ∘ e_{k-1}`` — one pallas launch.

    Each of the ``B·H`` heads is its own scan, and the ``(dh, dh)`` memory
    is tiled by ``MLSTM_ROW_BLOCK`` rows (the combine rescales it row by
    row): one whole head's memory is 4 MiB per chunk at dh=1024.
    """
    interpret = resolve_interpret(interpret)
    m0, C0, n0 = carry0
    nc, B, H, dh = nhat.shape
    key = ("mlstm", la.shape, Chat.shape, str(la.dtype), block, interpret)

    def build():
        def rows(t, r, c):     # (nc, B, H, ...) → (B·H, nc, r, c)
            return t.reshape(nc, B * H, r, c).swapaxes(0, 1)

        def back(t):           # (B·H, nc, r, c) → (nc, B, H, ...)
            return t.swapaxes(0, 1).reshape((nc, B, H) + t.shape[2:])

        def run(la, mS, Chat, nhat, m0, C0, n0):
            ent = tree_scan(
                (rows(la, 1, 1), rows(mS, 1, 1), rows(Chat, dh, dh),
                 rows(nhat, 1, dh)),
                combine=logspace_affine_combine, units=LOGSPACE_UNITS,
                carry0=(jnp.zeros((B * H, 1, 1), la.dtype),
                        m0.reshape(B * H, 1, 1), C0.reshape(B * H, dh, dh),
                        n0.reshape(B * H, 1, dh)),
                inclusive=False, block=block, rblock=MLSTM_ROW_BLOCK,
                interpret=interpret, kind="ssm_scan")
            la_e, m_e, C_e, n_e = (back(t) for t in ent)
            return (la_e.reshape(nc, B, H), m_e.reshape(nc, B, H),
                    C_e, n_e.reshape(nc, B, H, dh))
        return run

    return _cached(key, build)(la, mS, Chat, nhat, m0, C0, n0)


def mlstm_carry_scan_ref(la, mS, Chat, nhat, carry0):
    """Sequential-fold oracle for the exclusive carry scan."""
    m0, C0, n0 = carry0
    c = (jnp.zeros_like(m0), m0, C0, n0)

    def body(c, e):
        return logspace_affine_combine(c, e), c

    _, ent = jax.lax.scan(body, c, (la, mS, Chat, nhat))
    return ent


__all__ = [
    "LOG_ZERO", "affine_combine", "AFFINE_UNITS",
    "logspace_affine_combine", "LOGSPACE_UNITS",
    "mamba_assoc_scan", "mamba_assoc_scan_ref", "mamba_seq_scan_ref",
    "mlstm_carry_scan", "mlstm_carry_scan_ref",
]
