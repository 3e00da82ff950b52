"""Fixtures for the benchmark's CPU tests.

``tiny_root`` is a checkout-shaped directory: a copy of ``bench/`` with the
tiny configuration, mix and cell of ``bench/tests/data`` added as new files
beside the real ones, and a ``BENCHMARK.json`` that names only the tiny
cell.  Runs there use the real harness on the CPU with the chip check
skipped, at widths a test can hold.
"""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TINY_CELL = "tiny-gqa.chat"


def tiny_benchmark() -> dict:
    return {
        "command": ["python3", "-m", "bench.run"],
        "paths": ["bench"],
        "run_seconds": 6,
        "configs": [{"name": "tiny-gqa", "source": "test-only",
                     "file": "bench/configs/tiny-gqa.json", "reduced": [],
                     "why": "test-only"}],
        "workloads": [{"name": TINY_CELL, "config": "tiny-gqa",
                       "traffic": "tiny-chat", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("tpot_p90_ms", "ms"), ("setup_s", "s"))],
        "per_layer": [{"name": "decode_step_ms.chat", "unit": "ms",
                       "better": "lower", "source": "device_trace",
                       "layer": "decode tick", "moves": "tpot_p90_ms"},
                      {"name": "ttft_p90_ms.chat", "unit": "ms",
                       "better": "lower", "source": "host_clock",
                       "layer": "engine", "moves": "tpot_p90_ms"}],
    }


def make_tiny_root(dst: Path) -> Path:
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for sub in ("configs", "traffic", "workloads"):
        for f in (DATA / sub).glob("*.json"):
            shutil.copy(f, dst / "bench" / sub / f.name)
    (dst / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark()))
    return dst


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_tiny_root(tmp_path)


def run_tiny(root: Path, seed: int = 5, trace: bool = False, **kw) -> dict:
    """One CPU run of the tiny cell through the real harness."""
    from bench.harness import run_cell
    return run_cell(root, TINY_CELL, seed, 4.0, trace,
                    t_start=time.perf_counter(), require_tpu=False,
                    use_cache=False, **kw)
