"""The serving driver: one cell's run through ``ContinuousEngine``
(open-loop or standing-backlog mixes).

Set-up makes the weights from the seed, builds the engine, and warms every
shape the cell's traffic can produce.  The window then drives
``submit``/``step`` against the mix's schedule (open loop or a standing
backlog) for ``--seconds``; open-loop requests due in the window are drained
after it, for at most ``DRAIN_GRACE_S``.  The harness records, around the
engine's own calls, what the per-layer metrics need: each prefill call's
positions and each decode tick's live lanes.  Nothing of the engine is
changed.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import correctness
from bench import traffic as traffic_gen

DRAIN_GRACE_S = 60.0


@dataclasses.dataclass
class Served:
    """One request of the window, as the harness saw it."""
    rid: int
    due: float                    # host clock (perf_counter)
    prompt: np.ndarray
    max_new: int
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    result: Optional[np.ndarray] = None


@dataclasses.dataclass
class Calls:
    """Host-side record of the engine's prefill and decode calls."""
    prefill: List[Tuple[float, float, int, int, int]] = dataclasses.field(
        default_factory=list)     # (t0, t1, prompt length, start, stop)
    ticks: List[Tuple[float, float, List[Tuple[int, int]]]] = \
        dataclasses.field(default_factory=list)   # (t0, t1, [(cached, live)])

    def between(self, t0: float, t1: float) -> "Calls":
        return Calls([c for c in self.prefill if t0 <= c[0] < t1],
                     [c for c in self.ticks if t0 <= c[0] < t1])


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def _instrument(engine, calls: Calls) -> None:
    """Wrap the engine's prefill and decode-tick calls (instance attributes
    shadow the methods) to record positions and live lanes."""
    run = engine.prefiller.run
    tick = engine._decode_tick

    def prefill_run(params, toks, cache, **kw):
        t0 = time.perf_counter()
        with _span("bench.prefill_call"):
            out = run(params, toks, cache, **kw)
        st = out[2]
        stop = st.next_start if st.preempted else toks.shape[1]
        calls.prefill.append((t0, time.perf_counter(),
                              int(kw["row_lengths"][0]), int(kw.get("start",
                                                                     0)),
                              int(stop)))
        return out

    def decode_tick():
        before = {i: (len(s.req.prompt) + len(s.emitted), len(s.emitted))
                  for i, s in enumerate(engine.slots) if s is not None}
        n0 = engine.telemetry.ticks
        t0 = time.perf_counter()
        with _span("bench.decode_tick_call"):
            tick()
        if engine.telemetry.ticks == n0:
            return
        lanes = []
        for i, (cached, e0) in before.items():
            live = len(engine.slots[i].emitted) - e0
            if live:
                lanes.append((cached, live))
        calls.ticks.append((t0, time.perf_counter(), lanes))

    engine.prefiller.run = prefill_run
    engine._decode_tick = decode_tick


def _chunk_sizes(policy) -> List[int]:
    return list(range(policy.align, policy.cap + 1, policy.align))


def _warm_prompts(policy, longest: int) -> List[int]:
    """Padded prompt lengths whose by_blocks schedules, together, run every
    chunk size the prefill can produce (a resumed prefill restarts its
    schedule, so any multiple of ``align`` up to ``cap`` can occur)."""
    from repro.core.plan import geometric_blocks
    need = set(_chunk_sizes(policy))
    out: List[int] = []
    for S in range(policy.align, longest + 1, policy.align):
        got = {b - a for a, b in geometric_blocks(
            S, first=policy.first, growth=policy.growth,
            align=policy.align, cap=policy.cap)}
        if got & need:
            out.append(S)
            need -= got
        if not need:
            return out
    raise ValueError(f"chunk sizes {sorted(need)} need prompts longer than "
                     f"{longest}")


class ServeCell:
    """One serving run: ``setup()``, ``window()``, ``drain()``, then
    ``release()`` hands back what the comparison needs and frees the
    engine's device state."""

    def __init__(self, spec, seed: int, seconds: float):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.mix = spec.traffic
        self.calls = Calls()
        self.served: List[Served] = []
        self.finished: Dict[int, Served] = {}

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.serve.engine import ContinuousEngine, EngineConfig
        fam = self.spec.family_module("systems")
        m = self.spec.model_cfg
        self.model = fam.build_model(m)
        self.params = fam.make_params(self.model, m, self.seed)
        jax.block_until_ready(self.params)
        self.ecfg = EngineConfig(**self.spec.config["engine"])
        self.engine = ContinuousEngine(self.model, self.params, self.ecfg)
        self.items = traffic_gen.generate(self.mix, m["vocab_size"],
                                          self.seed, self.seconds)
        self._warm()
        _instrument(self.engine, self.calls)

    def _warm(self) -> None:
        """Every shape of the window, once: each prefill chunk size through
        the engine's own prefiller (whole schedules, so that no budget cuts
        one short), requests through the engine for the slot insert and a
        full decode tick, and the token slices of every padded prompt length
        of the mix."""
        from repro.serve.engine import Request
        eng, ecfg = self.engine, self.ecfg
        pol = eng.prefiller.policy
        rng = np.random.default_rng(0)
        vocab = self.spec.model_cfg["vocab_size"]
        for S in _warm_prompts(pol, ecfg.max_seq - ecfg.decode_tick - 1):
            toks = jnp.asarray(rng.integers(3, vocab, (1, S), dtype=np.int32))
            out = eng.prefiller.run(eng.params, toks,
                                    self.model.init_cache(1, ecfg.max_seq),
                                    row_lengths=[S])
            jax.block_until_ready(out[:2])
        for i in range(ecfg.max_batch):
            eng.submit(Request(rid=-1 - i, prompt=rng.integers(
                3, vocab, pol.align, dtype=np.int32),
                max_new=ecfg.decode_tick + 1))
        while eng.pending:
            eng.step()
        padded = sorted({-(-L // pol.align) * pol.align
                         for L in traffic_gen.prompt_lengths(self.mix,
                                                             self.seconds)})
        for S in padded:
            toks = jnp.asarray(np.zeros((1, S), np.int32))
            for c in _chunk_sizes(pol):
                if c <= S:
                    toks[:, 0:c].block_until_ready()
        jax.block_until_ready(eng.cache)

    # ------------------------------------------------------------- window
    def _submit(self, it: traffic_gen.Item, due: float) -> None:
        from repro.serve.engine import Request
        rid = len(self.served)
        self.served.append(Served(rid, due, it.prompt, it.max_new))
        with _span("bench.submit"):
            self.engine.submit(Request(rid=rid, prompt=it.prompt,
                                       max_new=it.max_new, t_submit=due))

    def _step(self) -> None:
        with _span("bench.engine_step"):
            for r in self.engine.step():
                s = self.served[r.rid]
                s.t_first, s.t_done, s.result = r.t_first, r.t_done, r.result
                self.finished[r.rid] = s

    def window(self) -> Tuple[float, float]:
        """Drive the engine for the window; returns its (start, end) on the
        host clock.  The window ends at the first step boundary at or after
        ``seconds``."""
        eng, items = self.engine, self.items
        self.counters0 = eng.telemetry.snapshot()
        t0 = time.perf_counter()
        end = t0 + self.seconds
        i = 0
        backlog = self.mix["kind"] == "backlog"
        with _span("bench.window"):
            while True:
                now = time.perf_counter()
                if backlog:
                    while len(eng.queue) < self.mix["queue_depth"]:
                        self._submit(items[i % len(items)], now)
                        i += 1
                else:
                    while i < len(items) and t0 + items[i].due_s <= now:
                        self._submit(items[i], t0 + items[i].due_s)
                        i += 1
                if now >= end:
                    break
                if eng.pending:
                    self._step()
                elif i < len(items):
                    with _span("bench.arrival_wait"):
                        time.sleep(max(0.0, min(end, t0 + items[i].due_s)
                                       - time.perf_counter()))
                else:
                    with _span("bench.arrival_wait"):
                        time.sleep(max(0.0, end - time.perf_counter()))
        t1 = time.perf_counter()
        self.counters1 = eng.telemetry.snapshot()
        self.t0, self.t1 = t0, t1
        return t0, t1

    def drain(self) -> None:
        """Open loop: serve every request due in the window, for at most
        DRAIN_GRACE_S past its close.  A backlog is not drained: its
        throughput is the window's, and requests in flight at the close are
        neither counted nor compared."""
        if self.mix["kind"] == "backlog":
            return
        stop = time.perf_counter() + DRAIN_GRACE_S
        while self.engine.pending and time.perf_counter() < stop:
            self._step()
        self.t_drained = time.perf_counter()

    # ------------------------------------------------------------ results
    def tally(self) -> Tuple[int, int]:
        """(attempted, failed): the window's requests, and those of them
        that were never served."""
        reqs = self.window_requests()
        return len(reqs), sum(r.result is None for r in reqs)

    def compare(self, control: bool, release: bool = True) -> Dict:
        """The window's sample, drawn from the seed and holding the longest
        request, against the f32 reference (and with ``control``, the fp8
        control's first tokens against it too)."""
        lim = self.spec.workload["correct"]
        picked = correctness.sample(self.window_requests(), self.seed,
                                    max_requests=lim["max_requests"],
                                    min_tokens=lim["min_tokens"])
        if release:
            self.release()
        if not picked:
            # nothing served: nothing to compare, and a reading no limit
            # admits
            out = {"max_logit_gap": 1e30, "compared_tokens": 0}
            if control:
                out["control_max_logit_gap"] = 1e30
            return out
        ref = self.spec.family_module("reference").Reference(
            self.spec.model_cfg, self.seed)
        return correctness.compare(ref, picked, control=control)

    def checks(self, compared: Dict, control: bool) -> Dict[str, Dict]:
        """The widest logit gap of the served tokens (of the control's first
        tokens, for a control run) and the unserved count, each with its
        limit."""
        gap = compared["control_max_logit_gap" if control
                       else "max_logit_gap"]
        return {
            "max_logit_gap": {"value": gap, "limit": self.spec.workload[
                "correct"]["max_logit_gap"]},
            "unserved": {"value": self.tally()[1], "limit": 0},
        }

    def window_requests(self) -> List[Served]:
        """The requests the window is judged on: open loop, every request
        due in it; backlog, those that finished in it."""
        if self.mix["kind"] == "backlog":
            return [s for s in self.finished.values() if s.t_done <= self.t1]
        return list(self.served)

    def reseed(self, seed: int) -> None:
        """Serve another seed on the same warm engine (calibration only):
        free the weights, make the new seed's, draw its traffic.  A backlog
        leaves work behind its window: what is still queued (never admitted,
        it holds nothing) is dropped and what is in flight finished first."""
        if self.mix["kind"] == "backlog":
            self.engine.queue.clear()
            while self.engine.pending:
                self._step()
        if self.engine.pending:
            raise RuntimeError("reseed() needs an idle engine")
        self.engine.params = self.params = None
        gc.collect()
        self.seed = seed
        m = self.spec.model_cfg
        self.params = self.spec.family_module("systems").make_params(
            self.model, m, seed)
        self.engine.params = self.params
        self.items = traffic_gen.generate(self.mix, m["vocab_size"], seed,
                                          self.seconds)
        self.served, self.finished = [], {}
        self.calls.prefill.clear()
        self.calls.ticks.clear()

    def release(self) -> None:
        """Free the engine's device state before the reference runs."""
        for name in ("engine", "params", "model"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()


Driver = ServeCell

__all__ = ["Driver", "ServeCell", "Served", "Calls", "DRAIN_GRACE_S"]
