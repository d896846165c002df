"""FLOPs and bytes from shapes, against counts made by hand."""
import json
import os

import pytest
from conftest import CHIP

import counts
import manifest


def dims(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        c = json.load(f)
    return manifest.reference(c).dims(c["published"])


def test_qwen3_layer_and_weights_by_hand():
    m = dims("qwen3-0.6b")
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, MLP 3 x 1024x3072
    per_layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 \
        + 3 * 1024 * 3072
    assert counts.layer_params(m) == per_layer == 15_728_640
    assert counts.weight_bytes(m) == 4 * (28 * per_layer + 1024 * 151936)
    # one token, one layer: K and V of 8 heads x 128, float32
    assert counts.kv_token_bytes(m) == 28 * 2 * 8 * 128 * 4


def test_deepseek_layer_and_weights_by_hand():
    m = dims("deepseek-coder-33b-l4")
    # q and o 7168x7168, k and v 7168x1024, MLP 3 x 7168x19200
    per_layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    assert counts.layer_params(m) == per_layer == 530_317_312
    assert counts.weight_bytes(m) == 4 * (4 * per_layer + 7168 * 32256)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "deepseek-coder-33b-l4"])
def test_decode_and_prefill_flops_by_hand(name):
    m = dims(name)
    mat = 2 * (m["L"] * counts.layer_params(m) + m["d"] * m["V"])
    attn = m["L"] * 4 * m["hq"] * m["hd"]
    assert counts.decode_flops(m, 100) == mat + 100 * attn
    n = 64
    assert counts.prefill_flops(m, n) == (
        2 * n * m["L"] * counts.layer_params(m) + 2 * m["d"] * m["V"]
        + attn * n * (n + 1) // 2)


def test_attend_step_counts_live_lengths_only():
    m = dims("qwen3-0.6b")
    f, b = counts.attend_step(m, [10, 20])
    assert f == counts.decode_flops(m, 11) + counts.decode_flops(m, 21)
    kv = counts.kv_token_bytes(m)
    assert b == counts.weight_bytes(m) + 30 * kv + 2 * kv


def test_gather_needs_live_blocks_read_and_written():
    # lengths 0, 7, 8, 15 with 8-token blocks: 1, 1, 2, 2 blocks
    assert counts.live_blocks([0, 7, 8, 15], 8) == 6
    assert counts.gather_step(65536, 8192, 6 * 28, 28) == \
        6 * 28 * 2 * 65536 + 28 * 8192
