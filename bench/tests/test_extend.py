"""A configuration, a mix, a cell, a per-layer metric and a driver are
added as new files under bench/ (and entries in BENCHMARK.json), and the
harness runs the new cell and reports the new metric without an edit to any
file that was there."""

import json
import time

from bench.harness import run_cell
from bench.tests.conftest import TINY_CELL, run_tiny


def test_new_files_only(tiny_root):
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    (tiny_root / "bench" / "metrics" / "prompt_tokens_k.py").write_text(
        '"""Thousands of prompt tokens due in the window."""\n\n\n'
        "def read(run):\n"
        "    return sum(len(s.prompt) for s in run.cell.window_requests())"
        " / 1e3\n")
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "prompt_tokens_k", "unit": "ktokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "traffic", "moves": "tpot_p90_ms",
                           "workloads": [TINY_CELL]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))
    res = run_tiny(tiny_root, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["prompt_tokens_k"]["value"] > 0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


OUTPUT_TOK_S = (
    '"""Tokens emitted in the window over its length."""\n\n\n'
    "def read(run):\n"
    "    c = run.cell\n"
    "    d = c.counters1['useful_decoded'] - c.counters0['useful_decoded']\n"
    "    firsts = sum(1 for s in c.served if s.t_first is not None\n"
    "                 and c.t0 <= s.t_first <= c.t1)\n"
    "    return (d + firsts) / run.window_s\n")


def test_new_backlog_cell(tiny_root):
    """A standing-backlog cell added by files alone: its throughput metric
    is read and its served tokens pass the comparison."""
    (tiny_root / "bench" / "metrics" / "output_tok_s.py").write_text(
        OUTPUT_TOK_S)
    (tiny_root / "bench" / "workloads" / "tiny-gqa.batch.json").write_text(
        (tiny_root / "bench" / "workloads" / f"{TINY_CELL}.json")
        .read_text())
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-gqa.batch", "config": "tiny-gqa",
                           "traffic": "tiny-backlog", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] != "setup_s":
            m["workloads"] = [TINY_CELL]
    b["end_to_end"].append(
        {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["tiny-gqa.batch"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))
    res = run_cell(tiny_root, "tiny-gqa.batch", 3, 4.0, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   use_cache=False)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["attempted"] > 0


TRAIN_DRIVER = '''"""A training driver: SGD on a bigram softmax model, judged on the loss
of its first three steps against a float64 numpy reference."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

LR = 0.5


def _loss(w, toks):
    x, y = toks[:, :-1], toks[:, 1:]
    logp = jax.nn.log_softmax(w[x], -1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def _ref_losses(w, batches):
    w = np.asarray(w, np.float64)
    out = []
    for toks in batches:
        x, y = toks[:, :-1].ravel(), toks[:, 1:].ravel()
        z = w[x] - w[x].max(-1, keepdims=True)
        p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
        out.append(-np.log(p[np.arange(len(y)), y]).mean())
        p[np.arange(len(y)), y] -= 1.0
        g = np.zeros_like(w)
        np.add.at(g, x, p / len(y))
        w = w - LR * g
    return out


class Driver:
    def __init__(self, spec, seed, seconds):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.vocab = spec.model_cfg["vocab_size"]
        self.losses = []

    def batch(self, i):
        return traffic.batch_tokens(self.spec.traffic, self.vocab,
                                    self.seed, i)

    def setup(self):
        key = jax.random.PRNGKey(self.seed)
        self.w0 = 0.01 * jax.random.normal(key, (self.vocab, self.vocab))
        grad = jax.value_and_grad(_loss)
        self.step = jax.jit(lambda w, t: (lambda lg: (w - LR * lg[1], lg[0]))(
            grad(w, t)))
        jax.block_until_ready(self.step(self.w0, self.batch(0)))

    def window(self):
        w, t0 = self.w0, time.perf_counter()
        while time.perf_counter() < t0 + self.seconds:
            w, loss = self.step(w, self.batch(len(self.losses)))
            self.losses.append(float(loss))
        return t0, time.perf_counter()

    def drain(self):
        pass

    def tally(self):
        return len(self.losses), 0

    def compare(self, control, release=True):
        ref = _ref_losses(self.w0, [self.batch(i) for i in range(3)])
        return {"loss_gap": max(abs(a - b) / abs(b)
                                for a, b in zip(self.losses, ref))}

    def checks(self, compared, control):
        return {"loss_gap": {"value": compared["loss_gap"], "limit": 1e-4}}
'''


def test_new_training_driver(tiny_root):
    """A cell of a new kind, a training run, added by files alone: its
    driver, mix and metric are found by name, and its comparison decides
    ``correct``."""
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    bench = tiny_root / "bench"
    (bench / "drivers" / "tiny_train.py").write_text(TRAIN_DRIVER)
    (bench / "traffic" / "tiny-batches.json").write_text(json.dumps(
        {"driver": "tiny_train", "kind": "batches", "batch": 4, "seq": 16}))
    (bench / "workloads" / "tiny-gqa.train.json").write_text("{}")
    (bench / "metrics" / "train_steps_per_s.py").write_text(
        '"""Optimizer steps completed per second of the window."""\n\n\n'
        "def read(run):\n"
        "    return len(run.cell.losses) / run.window_s\n")
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-gqa.train", "config": "tiny-gqa",
                           "traffic": "tiny-batches", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] != "setup_s":
            m["workloads"] = [TINY_CELL]
    b["end_to_end"].append(
        {"name": "train_steps_per_s", "unit": "steps/s", "better": "higher",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["tiny-gqa.train"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))
    res = run_cell(tiny_root, "tiny-gqa.train", 3, 2.0, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   use_cache=False)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_steps_per_s", "setup_s"}
    assert res["attempted"] >= 3
    assert res["checks"]["loss_gap"]["value"] < 1e-5
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
