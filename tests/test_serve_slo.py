"""Multi-tenant SLO serving: admission ordering, per-class caps, deadline
shedding, hot policy swap, slot death, bounded queues, config validation and
the telemetry EWMA cold-start fix."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.model import Model
from repro.serve.engine import (ContinuousEngine, Engine, EngineConfig,
                                EngineTelemetry, QueueFull, Request)
from repro.serve.slo import (DeadlineServePolicy, FifoServePolicy,
                             PriorityServePolicy)

KEY = jax.random.PRNGKey(0)
EOS = 7
MAX_SEQ = 224


def fp32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = fp32(get_smoke_config("llama3-8b"))
    model = Model(cfg)
    params = model.init(KEY)
    return model, params


def _req(rid, vocab, *, n=12, max_new=6, **kw):
    rng = np.random.RandomState(100 + rid)
    return Request(rid=rid, prompt=rng.randint(8, vocab, size=n)
                   .astype(np.int32), max_new=max_new, **kw)


def _drain(eng, max_steps=400):
    out = []
    for _ in range(max_steps):
        if not eng.pending:
            return out
        out.extend(eng.step())
    raise AssertionError(f"engine did not drain in {max_steps} steps")


def _cont(model, params, policy=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("eos_id", EOS)
    kw.setdefault("max_seq", MAX_SEQ)
    return ContinuousEngine(model, params, EngineConfig(**kw), policy=policy)


def _one_at_a_time(model, params, reqs):
    refs = {}
    for r in reqs:
        eng = Engine(model, params,
                     EngineConfig(max_batch=1, eos_id=EOS, max_seq=MAX_SEQ))
        eng.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new))
        (done,) = eng.step()
        refs[r.rid] = np.asarray(done.result)
    return refs


# ---------------------------------------------------------------------------
# EngineConfig validation (loud, at construction)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(max_batch=0), "max_batch"),
    (dict(max_batch=2, prefill_block_budget=0), "prefill_block_budget"),
    (dict(max_batch=2, decode_tick=0), "decode_tick"),
    (dict(max_batch=4, max_queue=2), "max_queue"),
    (dict(max_batch=2, class_caps={"streaming": 1}), "class_caps"),
    (dict(max_batch=2, class_caps={"batch": 0}), "class_caps"),
])
def test_engine_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(eos_id=EOS, **kw)


# ---------------------------------------------------------------------------
# Telemetry EWMA cold start
# ---------------------------------------------------------------------------

def test_ewma_first_observation_seeds_directly():
    t = EngineTelemetry()
    t.observe_decode(useful=4, seconds=0.4, steps=1)
    assert t.decode_s_per_token == 0.1          # seeded, NOT 0.25 * 0.1
    t.observe_decode(useful=4, seconds=0.8, steps=1)
    assert t.decode_s_per_token == (1 - t.ewma) * 0.1 + t.ewma * 0.2
    t2 = EngineTelemetry()
    t2.observe_admission(pages=6)
    assert t2.pages_per_request == 6.0
    t2.observe_prefill(blocks=0, seconds=0.5)   # no-op: no work
    assert t2.prefill_s_per_block == 0.0 and "prefill_s_per_block" \
        not in t2._seeded


def test_ewma_zero_first_sample_is_still_seeded():
    """A genuine ~0.0 first sample must count as seeded (the old
    ``old == 0.0`` sentinel would re-seed forever)."""
    t = EngineTelemetry()
    t.observe_decode(useful=4, seconds=0.0, steps=1)
    assert t.decode_s_per_token == 0.0
    t.observe_decode(useful=4, seconds=0.4, steps=1)
    assert t.decode_s_per_token == t.ewma * 0.1  # mixed with the seeded 0.0


# ---------------------------------------------------------------------------
# Bounded queues
# ---------------------------------------------------------------------------

def test_sync_engine_max_queue_rejects_loudly(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = Engine(model, params, EngineConfig(
        max_batch=2, eos_id=EOS, max_seq=MAX_SEQ, max_queue=2))
    eng.submit(_req(0, vocab))
    eng.submit(_req(1, vocab))
    with pytest.raises(QueueFull, match="max_queue"):
        eng.submit(_req(2, vocab))
    assert eng.telemetry.queue_rejections == 1
    assert [r.rid for r in eng.queue] == [0, 1]   # rejected one never queued


def test_continuous_engine_max_queue_and_unknown_class(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params, max_batch=2, max_queue=3)
    for i in range(3):
        eng.submit(_req(i, vocab))
    with pytest.raises(QueueFull):
        eng.submit(_req(3, vocab))
    assert eng.telemetry.queue_rejections == 1
    with pytest.raises(ValueError, match="SLO class"):
        eng.submit(_req(9, vocab, slo="streaming"))
    done = _drain(eng)
    assert sorted(r.rid for r in done) == [0, 1, 2]


# ---------------------------------------------------------------------------
# SLO admission ordering + per-class caps
# ---------------------------------------------------------------------------

def _first_admitted(eng, step_results):
    """rid of the request the engine admitted in a just-run step — whether
    it is still prefilling, already decoding, or retired within the step
    (the smoke model can serve a short request inside one step)."""
    if eng._job is not None:
        return eng._job.req.rid
    live = [s.req.rid for s in eng.slots if s is not None]
    if live:
        return live[0]
    return step_results[0].rid


def test_priority_policy_admits_interactive_first(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params, PriorityServePolicy())
    eng.submit(_req(0, vocab, slo="batch"))
    eng.submit(_req(1, vocab, slo="background"))
    eng.submit(_req(2, vocab, slo="interactive"))
    done = eng.step()
    assert _first_admitted(eng, done) == 2   # interactive jumped the queue
    done += _drain(eng)
    assert sorted(r.rid for r in done) == [0, 1, 2]


def test_deadline_policy_admits_earliest_deadline_first(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params, DeadlineServePolicy())
    eng.submit(_req(0, vocab, slo="batch", deadline_s=500.0))
    eng.submit(_req(1, vocab, slo="batch", deadline_s=50.0))
    done = eng.step()
    assert _first_admitted(eng, done) == 1
    done += _drain(eng)
    assert sorted(r.rid for r in done) == [0, 1]


def test_class_caps_bound_in_flight_concurrency(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params, class_caps={"batch": 1})
    for i in range(3):
        eng.submit(_req(i, vocab, slo="batch", max_new=4))
    done = []
    for _ in range(400):
        if not eng.pending:
            break
        in_flight = [j.req.slo for j in (eng._job, eng._parked)
                     if j is not None]
        in_flight += [s.req.slo for s in eng.slots if s is not None]
        assert in_flight.count("batch") <= 1   # the cap, every step
        done.extend(eng.step())
    assert sorted(r.rid for r in done) == [0, 1, 2]


# ---------------------------------------------------------------------------
# Deadline shedding
# ---------------------------------------------------------------------------

def test_expired_queue_entries_shed_with_counters(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params)
    eng.submit(_req(0, vocab, slo="batch", deadline_s=1e-9,
                    tenant="tenant-a"))
    eng.submit(_req(1, vocab, slo="background", deadline_s=1e-9,
                    tenant="tenant-b"))
    eng.submit(_req(2, vocab, slo="interactive"))
    eng.submit(_req(3, vocab, slo="batch", tenant="tenant-a"))
    done = _drain(eng)
    shed = [r for r in done if r.shed]
    served = [r for r in done if not r.shed]
    assert sorted(r.rid for r in shed) == [0, 1]
    assert sorted(r.rid for r in served) == [2, 3]
    for r in shed:                       # loud, accounted, empty result
        assert r.result.size == 0 and r.t_done is not None
    assert eng.telemetry.shed == 2
    assert eng.telemetry.shed_by_tenant == {"tenant-a": 1, "tenant-b": 1}
    assert eng.telemetry.shed_by_class == {"batch": 1, "background": 1}


def test_in_flight_work_is_never_shed(smoke_model):
    """Deadlines only gate the queue: once admitted, a request runs to
    completion even if its deadline passes mid-decode."""
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    eng = _cont(model, params, max_batch=1)
    eng.submit(_req(0, vocab, deadline_s=30.0, max_new=8))
    (done,) = _drain(eng)
    assert not done.shed and done.result.size > 0


# ---------------------------------------------------------------------------
# Class preemption: batch prefill parks for interactive work
# ---------------------------------------------------------------------------

def test_batch_prefill_parks_for_interactive_and_both_are_exact(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    batch = _req(0, vocab, n=96, max_new=6, slo="batch")
    inter = _req(1, vocab, n=12, max_new=6, slo="interactive")
    eng = _cont(model, params, PriorityServePolicy(), prefill_block_budget=1)
    eng.submit(batch)
    done = eng.step()
    assert eng._job is not None and eng._job.req.rid == 0   # still prefilling
    eng.submit(inter)
    done += eng.step()
    assert eng.telemetry.class_preemptions == 1
    assert eng._parked is not None and eng._parked.req.rid == 0
    done += _drain(eng)
    assert sorted(r.rid for r in done) == [0, 1]
    refs = _one_at_a_time(model, params, [batch, inter])
    for r in done:                       # parking never perturbs tokens
        np.testing.assert_array_equal(refs[r.rid], np.asarray(r.result))


# ---------------------------------------------------------------------------
# Hot policy swap
# ---------------------------------------------------------------------------

def test_set_policy_hot_swap_preserves_exactness(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    reqs = [_req(i, vocab, n=10 + 3 * i, max_new=5 + (i % 3),
                 slo=("interactive" if i % 3 == 0 else "batch"))
            for i in range(6)]
    eng = _cont(model, params, FifoServePolicy(), max_batch=2)
    for r in reqs:
        eng.submit(r)
    done = []
    for step in range(400):
        if not eng.pending:
            break
        done.extend(eng.step())
        if step == 1:
            eng.set_policy(PriorityServePolicy())   # live, mid-flight
    assert eng.telemetry.policy_swaps == 1
    assert isinstance(eng.policy, PriorityServePolicy)
    assert sorted(r.rid for r in done) == list(range(6))
    refs = _one_at_a_time(model, params, reqs)
    for r in done:
        np.testing.assert_array_equal(refs[r.rid], np.asarray(r.result))


# ---------------------------------------------------------------------------
# Slot death: requeue exactly once, tokens exact
# ---------------------------------------------------------------------------

def test_slot_death_requeues_once_with_exact_tokens(smoke_model):
    model, params = smoke_model
    vocab = model.cfg.vocab_size
    reqs = [_req(i, vocab, n=14 + 5 * i, max_new=10) for i in range(2)]

    undisturbed = {}
    eng0 = _cont(model, params, max_batch=2)
    for r in reqs:
        eng0.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new))
    for r in _drain(eng0):
        undisturbed[r.rid] = np.asarray(r.result)

    eng = _cont(model, params, max_batch=2)
    for r in reqs:
        eng.submit(r)
    killed = False
    done = []
    for _ in range(400):
        if not eng.pending:
            break
        done.extend(eng.step())
        if not killed:
            for i, s in enumerate(eng.slots):
                if s is not None and s.emitted:
                    assert eng.kill_slot(i)
                    killed = True
                    break
    assert killed and eng.kill_slot(0) is False   # empty lane: no-op
    assert eng.telemetry.slot_deaths == 1
    assert sorted(r.rid for r in done) == [0, 1]
    by_rid = {r.rid: r for r in done}
    assert sum(r.requeues for r in done) == 1     # exactly one re-serve
    for rid, ref in undisturbed.items():
        np.testing.assert_array_equal(ref, np.asarray(by_rid[rid].result))
