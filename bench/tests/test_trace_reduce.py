"""The trace reduction: busy as a union of program intervals, device time
by program, idle gaps attributed to the harness's innermost host span."""

import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000          # ns


def test_program_name():
    assert tr.program_name("jit_tick(12)") == "tick"
    assert tr.program_name("jit_chunk.3") == "chunk"
    assert tr.program_name("jit_cache_slot_insert") == "cache_slot_insert"
    assert tr.program_name("fusion.1") == "fusion"


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_is_a_union_and_time_goes_by_program():
    dev = {"/device:TPU:0": {"programs": [
        ("jit_tick(1)", 10 * MS, 30 * MS),      # 10..40
        ("jit_chunk(2)", 20 * MS, 10 * MS),     # 20..30, overlaps the tick
        ("jit_chunk(2)", 60 * MS, 20 * MS),     # 60..80
        ("jit_tick(1)", 95 * MS, 10 * MS),      # 95..105, cut at 100
    ]}}
    host = [("bench.window", 0, 100 * MS),
            ("bench.engine_step", 0, 50 * MS),
            ("bench.decode_tick_call", 5 * MS, 40 * MS),
            ("bench.arrival_wait", 80 * MS, 15 * MS),
            ("other", 0, 100 * MS)]
    r = tr.reduce_events(dev, host)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030 + 0.020 + 0.005)
    assert r["program_s"] == pytest.approx({"tick": 0.035, "chunk": 0.030})
    assert r["program_count"] == {"tick": 2, "chunk": 2}
    # gaps: 0..10 (in decode_tick_call, innermost), 40..60 (midpoint 50:
    # no bench span), 80..95 (arrival_wait)
    assert r["longest_gaps"] == [["none", pytest.approx(0.020)],
                                 ["arrival_wait", pytest.approx(0.015)],
                                 ["decode_tick_call", pytest.approx(0.010)]]
    assert r["idle_gaps"] == r["longest_gaps"]     # one gap per span here
    assert r["idle_by_host_span_s"]["arrival_wait"] == pytest.approx(0.015)
    assert r["device_ops"][0] == ["tick", pytest.approx(0.035)]


def test_devices_are_averaged_and_collectives_counted():
    dev = {f"/device:TPU:{i}": {
        "programs": [("jit_step", 0, (50 + 10 * i) * MS)],
        "ops": [("all-reduce.1", 0, 5 * MS), ("fusion.2", 5 * MS, 5 * MS)]}
        for i in range(2)}
    r = tr.reduce_events(dev, [("bench.window", 0, 100 * MS)])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["collective_s"] == pytest.approx(0.005)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_events({}, [("bench.engine_step", 0, 1)])


def test_recorded_chip_trace():
    """A 1.5 s slice of a traced minitron-4b.chat window on a v5e: the
    decode tick (unnamed, ``jit__unknown``) and the prefill chunks are found
    by name, busy lies inside the window, and every gap is attributed."""
    rec = json.loads((DATA / "trace_events.json").read_text())
    host = [tuple(e) for e in rec["host"]]
    dev = {d: {k: [tuple(e) for e in v] for k, v in lines.items()}
           for d, lines in rec["devices"].items()}
    r = tr.reduce_events(dev, host)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["program_s"]["_unknown"] > 0 and r["program_s"]["chunk"] > 0
    assert r["busy_s"] <= sum(r["program_s"].values()) + 1e-12
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_by_host_span_s"].values()) == pytest.approx(idle)
