"""The traffic generator: same seed, same requests; every seed, the same
sizes and gaps in another order; open-loop requests all due in the window."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

HERE = Path(__file__).resolve().parent
MIXES = sorted((HERE.parent / "traffic").glob("*.json")) + sorted(
    (HERE / "data" / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**31 + 12345]


def _load(p):
    return json.loads(p.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(path, seed):
    mix = _load(path)
    a = traffic.generate(mix, 256000, seed, 30)
    b = traffic.generate(mix, 256000, seed, 30)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_share_sizes_and_gaps(path):
    mix = _load(path)
    runs = [traffic.generate(mix, 256000, s, 30) for s in SEEDS]
    sizes = [sorted((len(i.prompt), i.max_new) for i in r) for r in runs]
    # the same (prompt, max_new) pairs, not only the same lengths
    assert sizes[0] == sizes[1] == sizes[2]
    gaps = [sorted(np.round(np.diff([i.due_s for i in r]), 9)) for r in runs]
    # (a backlog's requests are all due at 0)
    assert gaps[0] == gaps[1] == gaps[2]
    orders = [[len(i.prompt) for i in r] for r in runs]
    assert orders[0] != orders[1]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_sizes_within_limits(path):
    mix = _load(path)
    for it in traffic.generate(mix, 256000, 3, 30):
        assert mix["prompt"]["min"] <= len(it.prompt) <= mix["prompt"]["max"]
        assert mix["max_new"]["min"] <= it.max_new <= mix["max_new"]["max"]
        assert it.prompt.min() >= mix["token_min"]
        assert it.prompt.max() < 256000


@pytest.mark.parametrize("seconds", [1.0, 10.0, 30.0, 51.0])
def test_open_loop_due_inside_window(seconds):
    mix = {"kind": "open_loop", "arrivals": "poisson", "rate_per_s": 3.3,
           "prompt": {"dist": "uniform", "min": 8, "max": 16},
           "max_new": {"dist": "uniform", "min": 4, "max": 8}}
    items = traffic.generate(mix, 100, 1, seconds)
    assert len(items) == int(3.3 * seconds)
    assert items[0].due_s == 0.0
    assert max(i.due_s for i in items) < seconds
    assert traffic.prompt_lengths(mix, seconds) == sorted(
        {len(i.prompt) for i in items})


@pytest.mark.parametrize("seed", SEEDS)
def test_training_batches_same_seed_same_rows(seed):
    mix = {"kind": "batches", "batch": 4, "seq": 16, "token_min": 3}
    a = traffic.batch_tokens(mix, 1000, seed, 2)
    assert a.shape == (4, 17) and a.dtype == np.int32
    assert np.array_equal(a, traffic.batch_tokens(mix, 1000, seed, 2))
    assert not np.array_equal(a, traffic.batch_tokens(mix, 1000, seed, 3))
    assert len({r.tobytes() for r in a}) == 4
    assert a.min() >= 3 and a.max() < 1000
    with pytest.raises(ValueError):
        traffic.batch_tokens(dict(mix, kind="backlog"), 1000, seed, 0)
