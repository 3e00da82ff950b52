"""Early-exit decoding — ``find_first`` (paper §4.1) as EOS detection.

Finding the EOS position of a batch of generations IS find_first: apply
``decode`` to positions until the predicate (tok == eos) fires.  The naive
schedule decodes every sequence to max_new_tokens (up to (P−1)/P of the work
wasted, in the paper's terms).  The by_blocks schedule decodes in
geometrically growing blocks, checking between blocks — total wasted work
bounded by half (growth=2), with O(log n) host synchronizations.

``decode_block`` runs n steps inside one jit (a ``work_loop`` grant);
finished sequences keep stepping until their block ends — exactly the
"tasks already started cannot be cancelled" semantics of classical
schedulers that the paper measures; the waste is *counted* and reported.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import geometric_blocks
from ..models.model import Model


@dataclasses.dataclass
class DecodeStats:
    blocks: int = 0
    steps_run: int = 0            # decode steps executed (per sequence)
    useful_tokens: int = 0        # tokens up to & including EOS
    wasted_tokens: int = 0        # tokens decoded past EOS
    all_finished: bool = False
    early_exit: bool = False      # retired by the entropy gate, not EOS

    @property
    def wasted_fraction(self) -> float:
        total = self.useful_tokens + self.wasted_tokens
        return self.wasted_tokens / total if total else 0.0


def make_decode_block(model: Model, eos_id: int):
    """Returns jit'd fn(params, tokens, cache, lengths, finished, n) →
    (tokens, cache, lengths, finished, out_block (B,n), wasted (B,)), the
    program ``decode_block`` (one compile per ``n``)."""

    def decode_block(params, tokens, cache, lengths, finished, n: int):
        B = tokens.shape[0]

        def body(i, carry):
            tokens, cache, lengths, finished, out, wasted = carry
            logits, cache = model.decode_step(params, tokens, cache, lengths)
            nxt = jnp.argmax(logits[:, :model.cfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            wasted = wasted + finished.astype(jnp.int32)
            out = out.at[:, i].set(jnp.where(finished, -1, nxt))
            finished = finished | (nxt == eos_id)
            lengths = lengths + 1
            return (nxt, cache, lengths, finished, out, wasted)

        out0 = jnp.full((B, n), -1, jnp.int32)
        wasted0 = jnp.zeros((B,), jnp.int32)
        return jax.lax.fori_loop(
            0, n, body, (tokens, cache, lengths, finished, out0, wasted0))

    return jax.jit(decode_block, static_argnames="n", donate_argnums=2)


def make_decode_tick(model: Model, eos_id: int):
    """Decode tick for the continuous-batching engine: like
    ``make_decode_block`` but each slot also carries ``remaining`` — its
    per-request ``max_new`` budget — so rows retire independently on EOS
    *or* budget exhaustion while the rest of the batch keeps stepping.

    Returns fn(params, tokens, cache, lengths, finished, remaining, n) →
    (tokens, cache, lengths, finished, remaining, out (B, n), wasted (B,)).
    Emitted tokens for rows that were already finished (or empty slots) are
    -1; ``lengths`` only advances for live rows, so slot KV stays aligned.
    The program is named ``tick`` (one compile per ``n``).
    """

    def tick(params, tokens, cache, lengths, finished, remaining, n: int):
        B = tokens.shape[0]

        def body(i, carry):
            tokens, cache, lengths, finished, remaining, out, wasted = carry
            live = ~finished
            logits, cache = model.decode_step(params, tokens, cache, lengths)
            nxt = jnp.argmax(logits[:, :model.cfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            wasted = wasted + finished.astype(jnp.int32)
            out = out.at[:, i].set(jnp.where(finished, -1, nxt))
            remaining = remaining - live.astype(jnp.int32)
            finished = finished | (nxt == eos_id) | (remaining <= 0)
            lengths = lengths + live.astype(jnp.int32)
            tokens = jnp.where(live, nxt, tokens)
            return (tokens, cache, lengths, finished, remaining, out, wasted)

        out0 = jnp.full((B, n), -1, jnp.int32)
        wasted0 = jnp.zeros((B,), jnp.int32)
        return jax.lax.fori_loop(
            0, n, body,
            (tokens, cache, lengths, finished, remaining, out0, wasted0))

    return jax.jit(tick, static_argnames="n", donate_argnums=2)


def make_gated_decode_tick(model: Model, eos_id: int, *, tau: float,
                           patience: int = 2):
    """Uncertainty-gated decode tick: EOS retirement plus an entropy gate.

    A lane whose predictive entropy stays below ``tau`` nats for
    ``patience`` consecutive live steps is *confident* — the model has
    committed to a low-uncertainty continuation — and retires early, so
    its decode lane (and its state slot) backfills from the queue.  The
    per-slot ``streak`` counter is threaded through the tick alongside the
    other slot state; ``gated`` reports which lanes the gate (not EOS /
    budget) retired this tick.

    Exactness property: gating only *stops* emission — every token emitted
    before the gate fires is the same greedy token the ungated tick
    produces, so a gated stream is an exact prefix of the ungated stream
    (pinned in tests/test_ssm_scan.py and BENCH_scan_ssm.json).

    Returns fn(params, tokens, cache, lengths, finished, remaining, streak,
    n) → (tokens, cache, lengths, finished, remaining, streak, gated,
    out (B, n), wasted (B,)), the program ``gated_tick``.
    """

    def gated_tick(params, tokens, cache, lengths, finished, remaining,
                   streak, n: int):
        B = tokens.shape[0]
        V = model.cfg.vocab_size

        def body(i, carry):
            (tokens, cache, lengths, finished, remaining, streak, gated,
             out, wasted) = carry
            live = ~finished
            logits, cache = model.decode_step(params, tokens, cache, lengths)
            lg = logits[:, :V]
            p = jax.nn.softmax(lg, axis=-1)
            ent = -jnp.sum(p * jnp.log(p + 1e-9), axis=-1)    # (B,) nats
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            wasted = wasted + finished.astype(jnp.int32)
            out = out.at[:, i].set(jnp.where(finished, -1, nxt))
            remaining = remaining - live.astype(jnp.int32)
            streak = jnp.where(live & (ent < tau), streak + 1, 0)
            gate = live & (streak >= patience)
            finished = finished | (nxt == eos_id) | (remaining <= 0) | gate
            gated = gated | gate
            lengths = lengths + live.astype(jnp.int32)
            tokens = jnp.where(live, nxt, tokens)
            return (tokens, cache, lengths, finished, remaining, streak,
                    gated, out, wasted)

        out0 = jnp.full((B, n), -1, jnp.int32)
        wasted0 = jnp.zeros((B,), jnp.int32)
        gated0 = jnp.zeros((B,), bool)
        return jax.lax.fori_loop(
            0, n, body,
            (tokens, cache, lengths, finished, remaining, streak, gated0,
             out0, wasted0))

    return jax.jit(gated_tick, static_argnames="n", donate_argnums=2)


def decode_until_eos(model: Model, params: Any, first_tokens: jnp.ndarray,
                     cache: Any, lengths: jnp.ndarray, *, eos_id: int,
                     max_new: int = 256, use_blocks: bool = True,
                     first_block: Optional[int] = None,
                     growth: float = 2.0, blockfn: Optional[Callable] = None
                     ) -> Tuple[jnp.ndarray, Any, DecodeStats]:
    """Greedy-decode until every sequence hits EOS (or max_new).

    use_blocks=False is the naive schedule (one block of max_new) — the
    paper's "without blocks" baseline, kept for the benchmark.

    Callers that decode repeatedly should build ``blockfn`` once with
    :func:`make_decode_block` and pass it in — the per-block jits live in
    the blockfn's cache, so a fresh one per call recompiles every block.
    """
    B = first_tokens.shape[0]
    stats = DecodeStats()
    if blockfn is None:
        blockfn = make_decode_block(model, eos_id)
    tokens = first_tokens
    finished = tokens == eos_id
    outs = []
    bounds = (geometric_blocks(max_new, first=first_block or max(8, B // 4),
                               growth=growth)
              if use_blocks else [(0, max_new)])
    wasted_total = 0
    for (lo, hi) in bounds:
        n = hi - lo
        tokens, cache, lengths, finished, out, wasted = blockfn(
            params, tokens, cache, lengths, finished, n)
        outs.append(out)
        stats.blocks += 1
        stats.steps_run += n
        wasted_total += int(wasted.sum())
        if bool(finished.all()):
            stats.all_finished = True
            break
    gen = jnp.concatenate(outs, axis=1)
    useful = int((gen >= 0).sum())
    stats.useful_tokens = useful
    # The kernel's per-block waste counter is the ground truth; it equals
    # steps_run·B − useful by construction (each step emits either a useful
    # token or a −1 for an already-finished row) — tested in test_serve.
    stats.wasted_tokens = wasted_total
    assert wasted_total == stats.steps_run * B - useful, \
        (wasted_total, stats.steps_run, B, useful)
    return gen, cache, stats


__all__ = ["decode_until_eos", "make_decode_block", "make_decode_tick",
           "make_gated_decode_tick", "DecodeStats"]
