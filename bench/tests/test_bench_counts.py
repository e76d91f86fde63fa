"""Hand counts of bitnet-0.73b's and granite-3-2b's bytes and operations."""

import json
import os

import pytest

from bench import counts, peaks
from bench.archs import ternary_dense as dense

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bitnet():
    return config("bitnet-0.73b")


@pytest.fixture(scope="module")
def granite():
    return config("granite-3-2b")


def test_packed_decoder_weights_are_136_mb(bitnet):
    # 24 x (4 x 1536^2 + 3 x 1536 x 4096) ternary weights, 8 bits per 5
    assert dense.linear_weights(bitnet) == 679_477_248
    assert dense.packed_weight_bytes(bitnet) == pytest.approx(135.9e6,
                                                               rel=1e-3)


def test_kv_per_token_is_147_kb(bitnet):
    # 24 layers x (K + V) x 24 heads x 64 x 2 bytes of bfloat16
    assert dense.kv_bytes_per_token(bitnet) == 147_456


def test_weight_pass_adds_the_float32_head(bitnet):
    head = 32000 * 1536 * 4
    norms = 49 * 1536 * 4
    assert counts.weight_pass_bytes(bitnet) == pytest.approx(
        dense.packed_weight_bytes(bitnet) + head + norms)


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


# granite-3-2b: 40 layers, d 2048, 32 query heads and 8 KV heads of 64,
# FF 8192, a tied 49155 x 2048 float32 head
GRANITE_LINEAR = 40 * (2048 * (2048 + 2 * 512) + 2048 * 2048
                       + 3 * 2048 * 8192)


def test_granite_linears_and_kv_per_token(granite):
    assert GRANITE_LINEAR == 2_432_696_320
    assert dense.linear_weights(granite) == 2_432_696_320
    # 40 layers x (K + V) x 8 heads x 64 x 2 bytes of bfloat16
    assert dense.kv_bytes_per_token(granite) == 81_920


def test_granite_weight_pass(granite):
    head = 49155 * 2048 * 4
    norms = 81 * 2048 * 4
    assert counts.weight_pass_bytes(granite) == pytest.approx(
        GRANITE_LINEAR / 5 + head + norms, rel=1e-15)
    assert counts.weight_pass_bytes(granite) == 889_880_576.0


@pytest.mark.parametrize("context", [1, 100, 2047])
def test_granite_decode_token_counts(granite, context):
    ops, moved = counts.decode_token(granite, context)
    assert ops == (2 * GRANITE_LINEAR + 40 * 4 * context * 2048
                   + 2 * 2048 * 49155)
    assert moved == (context + 1) * 81_920


@pytest.mark.parametrize("n,want", [(1, 5_067_059_200),
                                    (3, 14_799_482_880),
                                    (128, 625_676_922_880)])
def test_granite_prompt_ops(granite, n, want):
    assert counts.prompt_ops(granite, n) == want == (
        n * 2 * GRANITE_LINEAR + 40 * 4 * (n * (n + 1) // 2) * 2048
        + 2 * 2048 * 49155)
