"""The serving path's own tracing: ``EngineTelemetry.span`` host spans in a
profiler trace, per-request records, and the names of the device programs
and of the model's scopes."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.model import Model
from repro.serve.early_exit import (make_decode_block, make_decode_tick,
                                    make_gated_decode_tick)
from repro.serve.engine import (ContinuousEngine, EngineConfig,
                                EngineTelemetry, Request)

# each span's innermost enclosing span (None: outermost)
PARENT = {
    "serve.step": None,
    "serve.admit": "serve.step",
    "serve.prefill": "serve.step",
    "serve.install": "serve.step",
    "serve.install.wait": "serve.install",
    "serve.tick.sync": "serve.step",
    "serve.tick": "serve.step",
    "serve.tick.wait": "serve.tick",
    "serve.retire": "serve.step",
}


@pytest.fixture(scope="module")
def smoke_model():
    cfg = dataclasses.replace(get_smoke_config("llama3-8b"),
                              param_dtype="float32", compute_dtype="float32")
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(vocab, sizes=(130, 10, 11), max_news=(12, 4, 4)):
    rng = np.random.RandomState(1)
    return [Request(rid=i, prompt=rng.randint(3, vocab, size=n).astype(
        np.int32), max_new=m) for i, (n, m) in enumerate(zip(sizes, max_news))]


def _engine(smoke_model, **kw):
    model, params = smoke_model
    cfg = dict(max_batch=2, eos_id=7, max_seq=192, decode_tick=2,
               prefill_block_budget=1)
    cfg.update(kw)
    return ContinuousEngine(model, params, EngineConfig(**cfg))


def _drain(eng):
    done = []
    for _ in range(500):
        if not eng.pending:
            return done
        done += eng.step()
    raise AssertionError("engine did not drain in 500 steps")


@pytest.fixture(scope="module")
def traced(smoke_model, tmp_path_factory):
    """A budget-cut prompt and two short ones served under a CPU profiler
    trace: (engine, served requests, the trace's serve.* events)."""
    eng = _engine(smoke_model)
    reqs = _requests(eng.model.cfg.vocab_size)
    _drain(_engine(smoke_model))          # nothing to compile in the trace
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        for r in reqs:
            eng.submit(r)
        done = _drain(eng)
    finally:
        jax.profiler.stop_trace()
    (path,) = out.rglob("*.xplane.pb")
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats), line.name))
    return eng, done, events


def _parent(ev, events):
    name, s, e, _, line = ev
    holding = [(e2 - s2, n2) for n2, s2, e2, _, l2 in events
               if l2 == line and s2 <= s and e <= e2
               and (n2, s2, e2) != (name, s, e)]
    return min(holding)[1] if holding else None


def test_every_span_in_the_trace_nested(traced):
    _, _, events = traced
    assert {e[0] for e in events} == set(PARENT)
    for ev in events:
        assert _parent(ev, events) == PARENT[ev[0]], ev


def test_spans_carry_request_ids(traced):
    _, _, events = traced
    for name, _, _, args, _ in events:
        if name in ("serve.prefill", "serve.install", "serve.install.wait"):
            assert args["rid"] in (0, 1, 2), (name, args)
        if name == "serve.prefill":
            assert 0 <= args["start"] < args["stop"] and args["budget"] == 1
        if name == "serve.tick":
            assert 1 <= args["live"] <= 2
    admitted = [a["rid"] for n, _, _, a, _ in events
                if n == "serve.admit" and "rid" in a]
    assert sorted(admitted) == [0, 1, 2]


def test_budget_cut_prompt_counts_its_prefill_steps(traced):
    eng, done, events = traced
    spans = collections.Counter(a["rid"] for n, _, _, a, _ in events
                                if n == "serve.prefill")
    records = {r.rid: r for r in eng.telemetry.requests}
    assert records[0].prefill_steps >= 3      # 130 tokens, one block a step
    for rid, rec in records.items():
        assert rec.prefill_steps == spans[rid]
    assert sum(spans.values()) == eng.telemetry.snapshot()["prefill_calls"]
    assert eng.telemetry.prefills_completed == 3


def test_request_records_are_ordered(traced):
    eng, done, _ = traced
    records = list(eng.telemetry.requests)
    assert sorted(r.rid for r in records) == [0, 1, 2]
    by_rid = {r.rid: r for r in done}
    for rec in records:
        assert rec.t_submit <= rec.t_admit <= rec.t_first <= rec.t_done
        assert rec.tokens == len(by_rid[rec.rid].result)
        assert rec.prompt_len == len(by_rid[rec.rid].prompt)
        assert rec.t_done == by_rid[rec.rid].t_done


def test_blocking_phases_within_steps(traced):
    eng, _, events = traced
    snap = eng.telemetry.snapshot()
    blocked = sum(v for k, v in snap.items() if k.startswith("phase_s.")
                  and k.endswith((".wait", ".sync")))
    assert 0 < blocked <= snap["phase_s.step"]
    steps = sum(1 for e in events if e[0] == "serve.step")
    assert snap["steps"] == snap["phase_n.step"] == steps


def test_request_records_are_bounded(smoke_model, monkeypatch):
    monkeypatch.setattr(EngineTelemetry, "REQUEST_RECORDS", 2)
    eng = _engine(smoke_model, prefill_block_budget=None)
    for r in _requests(eng.model.cfg.vocab_size, (9, 10, 11, 12),
                       (2, 2, 2, 2)):
        eng.submit(r)
    done = _drain(eng)
    assert len(done) == 4
    assert [r.rid for r in eng.telemetry.requests] == \
        [r.rid for r in done][-2:]
    assert eng.telemetry.retired == 4


def test_requeued_request_is_admitted_afresh(smoke_model):
    eng = _engine(smoke_model, prefill_block_budget=None)
    (req,) = _requests(eng.model.cfg.vocab_size, (9,), (8,))
    eng.submit(req)
    eng.step()
    first_admit = req.t_admit
    assert first_admit is not None and req.prefill_steps == 1
    assert eng.kill_slot(next(i for i, s in enumerate(eng.slots) if s))
    assert req.t_admit is None and req.prefill_steps == 0
    _drain(eng)
    (rec,) = eng.telemetry.requests
    assert rec.t_admit > first_admit and rec.prefill_steps == 1


@pytest.mark.parametrize("name,make,lane_args", [
    ("tick", make_decode_tick, 1),                    # remaining
    ("gated_tick", lambda m, e: make_gated_decode_tick(m, e, tau=0.5), 2),
    ("decode_block", make_decode_block, 0),
])
def test_decode_programs_are_named(smoke_model, name, make, lane_args):
    """The decode programs carry their own names into the trace, and the
    model's named scopes into the ops' metadata."""
    model, params = smoke_model
    z = jnp.zeros((2,), jnp.int32)
    args = [params, z, model.init_cache(2, 64), z, jnp.zeros((2,), bool)]
    hlo = make(model, 7).lower(*args, *[z] * lane_args, 2).compile().as_text()
    assert hlo.startswith(f"HloModule jit_{name},")
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("attn.qkv", "attn.kv_write", "attn.attend", "attn.out",
                  "mlp", "head", "embed"):
        assert any(scope in op for op in ops), scope
