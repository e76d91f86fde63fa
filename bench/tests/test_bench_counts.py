"""Hand counts of bitnet-0.73b's bytes and operations."""

import json
import os

import pytest

from bench import counts, peaks

CFG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                   "bitnet-0.73b.json")


@pytest.fixture(scope="module")
def bitnet():
    with open(CFG) as f:
        return json.load(f)


def test_packed_decoder_weights_are_136_mb(bitnet):
    # 24 x (4 x 1536^2 + 3 x 1536 x 4096) ternary weights, 8 bits per 5
    assert counts.linear_weights(bitnet) == 679_477_248
    assert counts.packed_weight_bytes(bitnet) == pytest.approx(135.9e6,
                                                               rel=1e-3)


def test_kv_per_token_is_147_kb(bitnet):
    # 24 layers x (K + V) x 24 heads x 64 x 2 bytes of bfloat16
    assert counts.kv_bytes_per_token(bitnet) == 147_456


def test_weight_pass_adds_the_float32_head(bitnet):
    head = 32000 * 1536 * 4
    norms = 49 * 1536 * 4
    assert counts.weight_pass_bytes(bitnet) == pytest.approx(
        counts.packed_weight_bytes(bitnet) + head + norms)


@pytest.mark.parametrize("context", [1, 100, 2047])
def test_decode_token_counts(bitnet, context):
    ops, moved = counts.decode_token(bitnet, context)
    linear = 2 * 679_477_248
    attn = 24 * 4 * context * 1536
    head = 2 * 1536 * 32000
    assert ops == linear + attn + head
    assert moved == (context + 1) * 147_456


def test_prompt_ops_count_the_causal_prefix_and_one_head(bitnet):
    n = 3
    want = (n * 2 * 679_477_248 + 24 * 4 * (1 + 2 + 3) * 1536
            + 2 * 1536 * 32000)
    assert counts.prompt_ops(bitnet, n) == want


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["int8_ops"] == 393e12 and v5e["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
