"""The command refuses to run without a chip, and without the system."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "minitron-4b.chat", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env=None):
    return subprocess.run([sys.executable, "-m", "bench.run", *ARGS],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def test_cpu_exits_non_zero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(REPO, env)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr
    assert not p.stdout.strip(), "printed a result without a chip"


def test_only_the_benchmark_files_exits_non_zero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu",
                            PYTHONPATH=""))
    assert p.returncode != 0
    assert "system under test" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_json_names_existing_files():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in b["workloads"]:
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench" / "workloads" / f"{w['name']}.json").is_file()
