"""Needed operations and least bytes of minitron-4b against a hand count."""

import json
from pathlib import Path

from bench.flops import dense_gqa as f

M = json.loads((Path(__file__).resolve().parents[1] / "configs"
                / "minitron-4b.json").read_text())["model"]

# one layer's weights, by hand: q 3072x3072, k and v 3072x1024 each,
# o 3072x3072, MLP up 3072x9216 and down 9216x3072
LAYER = 3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072 + 2 * 3072 * 9216
HEAD = 3072 * 256000


def test_layer_params():
    assert f.layer_matmul_params(M) == LAYER == 81_788_928


def test_decode_one_lane_at_live_length():
    # one lane, 100 positions cached, live for 3 steps: step j attends to
    # 100 + j + 1 positions (101, 102, 103)
    got = f.decode_tick(M, [(100, 3)])
    attn = 32 * 4 * 24 * 128 * (101 + 102 + 103)
    assert got["flops"] == 3 * (32 * 2 * LAYER + 2 * HEAD) + attn
    weights = (32 * LAYER + HEAD + (2 * 32 + 1) * 3072
               + 32 * (9216 + 3072)) * 2
    kv = (101 + 102 + 103) * 32 * 2 * 8 * 128 * 2
    assert got["bytes"] == 3 * weights + kv
    assert got["steps"] == 3


def test_decode_weights_once_per_step_across_lanes():
    one = f.decode_tick(M, [(10, 8)])
    two = f.decode_tick(M, [(10, 8), (500, 2)])
    assert two["steps"] == 8
    kv_extra = (2 * 500 + 3) * f.kv_bytes_per_position(M)
    assert two["bytes"] - one["bytes"] == kv_extra


def test_prefill_head_at_last_position_only():
    L = 300
    whole = f.prefill_flops(M, L, 0, 320)          # padded to 320
    per_token = 32 * 2 * LAYER
    attn = 32 * 4 * 24 * 128 * (L * (L + 1) // 2)
    assert whole == L * per_token + attn + 2 * HEAD
    # split at a block boundary: the parts add up, the head in the last
    assert f.prefill_flops(M, L, 0, 96) + f.prefill_flops(M, L, 96, 320) \
        == whole
    assert f.prefill_flops(M, L, 0, 96) == 96 * per_token \
        + 32 * 4 * 24 * 128 * (96 * 97 // 2)
    # a block wholly past the prompt's end needs nothing
    assert f.prefill_flops(M, L, 300, 320) == 0
