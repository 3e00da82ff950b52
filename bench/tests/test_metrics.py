"""Every metric BENCHMARK.json names has a reader, found by its name, and
each reader reads what it should from a run record."""

import json
import types
from pathlib import Path

import pytest

from bench import harness
from bench.drivers.serve import Calls, Served

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
MODEL = json.loads((ROOT / "bench" / "configs" / "minitron-4b.json")
                   .read_text())["model"]
PEAK = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NAMES)
def test_reader_found_by_name(name):
    assert callable(reader(name).read)


def _run(trace):
    from bench.flops import dense_gqa
    served = [Served(0, 10.0, [1] * 100, 4, t_first=10.2, t_done=10.5,
                     result=[5, 6, 7, 8]),
              Served(1, 11.0, [1] * 50, 4, t_first=11.1, t_done=11.4,
                     result=[5, 6])]
    cell = types.SimpleNamespace(
        t0=10.0, t1=12.0, served=served, finished={},
        window_requests=lambda: served,
        counters0={"useful_decoded": 100, "decode_steps": 80},
        counters1={"useful_decoded": 164, "decode_steps": 96},
        ecfg=types.SimpleNamespace(max_batch=8, decode_tick=8),
        calls=Calls(prefill=[(10.1, 10.2, 100, 0, 128)],
                    ticks=[(10.3, 10.4, [(100, 8)]),
                           (11.2, 11.3, [(104, 8), (50, 1)])]))
    spec = types.SimpleNamespace(model_cfg=MODEL)
    return harness.Run(spec=spec, cell=cell, setup_s=12.5, window_s=2.0,
                       trace=trace, peak=PEAK, flops=dense_gqa)


def test_end_to_end_readers():
    run = _run(None)
    assert reader("setup_s").read(run) == 12.5
    tt = reader("ttft_p90_ms.chat").read(run)
    assert 100.0 < tt < 200.0
    tp = reader("tpot_p90_ms").read(run)
    assert 100.0 < tp < 300.0
    # 64 decoded tokens and two first tokens in a 2 s window
    assert reader("output_tok_s").read(run) == pytest.approx(33.0)


def test_lane_occupancy_from_counters():
    # 64 useful tokens over 16 steps of 8 lanes
    assert reader("lane_occupancy").read(_run(None)) == pytest.approx(50.0)


def test_per_layer_readers_need_a_trace():
    run = _run(None)
    for name in ("prefill_ms_per_ktok", "mfu.prefill", "decode_step_ms.chat",
                 "decode_hbm_roofline.chat", "mfu.decode.chat",
                 "idle_share.chat", "decode_step_ms.batch",
                 "decode_hbm_roofline", "mfu.decode", "idle_share.batch"):
        assert reader(name).read(run) is None


def test_per_layer_readers_from_a_trace():
    from bench.flops import dense_gqa as f
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "program_s": {"chunk": 0.05, "tick": 0.2}}
    run = _run(trace)
    for cell in ("chat", "batch"):
        assert reader(f"idle_share.{cell}").read(run) == pytest.approx(25.0)
        assert reader(f"decode_step_ms.{cell}").read(run) == \
            pytest.approx(200.0 / 16)
    assert reader("prefill_ms_per_ktok").read(run) == pytest.approx(
        50.0 / 0.1)
    assert reader("mfu.prefill").read(run) == pytest.approx(
        100 * f.prefill_flops(MODEL, 100, 0, 128) / (0.05 * 1.97e14))
    a = f.decode_tick(MODEL, [(100, 8)])
    b = f.decode_tick(MODEL, [(104, 8), (50, 1)])
    least = sum(max(t["bytes"] / 8.19e11, t["flops"] / 1.97e14)
                for t in (a, b))
    for name in ("decode_hbm_roofline.chat", "decode_hbm_roofline"):
        assert reader(name).read(run) == pytest.approx(100 * least / 0.2)
    for name in ("mfu.decode.chat", "mfu.decode"):
        assert reader(name).read(run) == pytest.approx(
            100 * (a["flops"] + b["flops"]) / (0.2 * 1.97e14))
