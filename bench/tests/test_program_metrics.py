"""The readers of the serving program's own records (``EngineTelemetry``
counters, spans and per-request records) on a traced run of the tiny chat
cell through the real harness, and the program's records against the
harness's own record of the same calls (``bench/drivers/serve.py``)."""

import json
import types
from pathlib import Path

import pytest

from bench import harness
from bench.tests.conftest import TINY_CELL, make_tiny_root, run_tiny

NEW = ("queue_wait_p90_ms.chat", "prefill_pieces.chat",
       "host_ms_per_step.chat")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny chat cell, traced, with the program's metrics named in its
    BENCHMARK.json; returns its result and what the readers saw: the
    harness's Run, and the engine's counters and records after the
    drain."""
    tiny_root = make_tiny_root(tmp_path_factory.mktemp("tiny"))
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for name in NEW:
        b["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "engine",
                               "moves": "tpot_p90_ms",
                               "workloads": [TINY_CELL]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))
    seen = {}
    read_metrics = harness.read_metrics

    def capture(run, section):
        seen["run"] = run     # the engine is released after the readers
        seen["after"] = run.cell.engine.telemetry.snapshot()
        seen["records"] = list(run.cell.engine.telemetry.requests)
        return read_metrics(run, section)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "read_metrics", capture)
        res = run_tiny(tiny_root, seed=11, trace=True)
    return res, seen


def test_program_metrics_read(traced):
    res, seen = traced
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in NEW:
        assert name in m, name        # read_metrics leaves out a None
    assert m["host_ms_per_step.chat"]["value"] > 0
    assert m["prefill_pieces.chat"]["value"] >= 1
    assert m["queue_wait_p90_ms.chat"]["value"] >= 0
    batch = seen["run"].spec.reader("host_ms_per_step.batch")
    assert batch.read(seen["run"]) == m["host_ms_per_step.chat"]["value"]


def test_program_records_match_the_harness(traced):
    """From the window's start to the drain's end, the engine's prefill
    and tick spans are the harness's prefill and tick calls, and its
    request records hold the harness's first and last token times."""
    _, seen = traced
    cell, after = seen["run"].cell, seen["after"]
    before = cell.counters0
    calls = cell.calls
    assert after["prefill_calls"] - before["prefill_calls"] \
        == len(calls.prefill)
    assert after["phase_n.tick"] - before["phase_n.tick"] == len(calls.ticks)
    records = {r.rid: r for r in seen["records"] if r.rid >= 0}
    served = cell.window_requests()
    assert sorted(records) == [s.rid for s in served]
    # each prompt's prefill pieces tile its padded length
    assert sum(r.prefill_steps for r in records.values()) \
        == len(calls.prefill)
    assert sum(stop - start for _, _, _, start, stop in calls.prefill) \
        == sum(-(-r.prompt_len // 32) * 32 for r in records.values())
    for s in served:
        r = records[s.rid]
        assert r.prompt_len == len(s.prompt)
        assert (r.t_first, r.t_done) == (s.t_first, s.t_done)
        assert s.due <= r.t_admit <= r.t_first


def test_program_metrics_absent_on_an_older_program():
    """A program whose engine keeps no spans, step counts or request
    records, as before they existed, reads None in every one of these
    metrics, and raises nothing."""
    old = {"ticks": 3, "decode_steps": 24, "prefill_blocks": 2}
    cell = types.SimpleNamespace(
        t0=0.0, t1=1.0, counters0=old, counters1=dict(old, ticks=9),
        window_requests=lambda: [],
        engine=types.SimpleNamespace(telemetry=types.SimpleNamespace()))
    run = harness.Run(spec=None, cell=cell, setup_s=1.0, window_s=1.0,
                      trace=None, peak=None, flops=None)
    root = Path(__file__).resolve().parents[1] / "metrics"
    for name in NEW + ("host_ms_per_step.batch",):
        reader = harness.load_module(root / f"{name}.py")
        assert reader.read(run) is None, name


def test_host_ms_per_step_leaves_out_device_bound_phases():
    """The blocking reads (``.wait``/``.sync``) and the prefill span, whose
    chunk dispatches wait on the device, come off the step's seconds;
    every other phase is host work and stays in."""
    c0 = {"steps": 5, "phase_s.step": 0.5, "phase_s.prefill": 0.2}
    c1 = {"steps": 15, "phase_s.step": 1.5, "phase_s.prefill": 0.6,
          "phase_s.tick.wait": 0.3, "phase_s.install.wait": 0.1,
          "phase_s.tick.sync": 0.05, "phase_s.install": 0.2,
          "phase_s.tick": 0.35, "phase_n.tick": 10}
    cell = types.SimpleNamespace(counters0=c0, counters1=c1)
    run = harness.Run(spec=None, cell=cell, setup_s=1.0, window_s=1.0,
                      trace=None, peak=None, flops=None)
    from bench import program_readers
    assert program_readers.host_ms_per_step(run) == pytest.approx(
        1e3 * (1.0 - 0.4 - 0.3 - 0.1 - 0.05) / 10)
