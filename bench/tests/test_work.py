"""The dense architecture's work counts (bench/arch/dense.py) against
values worked by hand for smollm-360m."""
import json

import pytest

from bench.spec import BENCH, architecture

dense = architecture("llama")


@pytest.fixture(scope="module")
def smollm():
    with open(BENCH / "configs" / "smollm-360m.json") as f:
        return dense.shapes(json.load(f))


def test_smollm_shapes(smollm):
    # Per layer: q 960x960, k and v 960x320 each, o 960x960, MLP 3x960x2560,
    # two norms of 960.
    per_layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560 + 2 * 960
    assert smollm.layer_params() == per_layer == 9_832_320
    assert smollm.head_params() == 960 * 49152
    # 32 layers x (K + V) x 5 heads x 64 x 2 bytes.
    assert smollm.kv_bytes_per_token() == 40_960


def test_one_smollm_decode_step(smollm):
    # 32 active rows, each at a context of 1000 positions.
    rows, ctx = 32, 1000
    n = 32 * 9_832_320 + 960 * 49152           # 361_820_160
    flops, nbytes = dense.decode_work(smollm, 1, rows * ctx, rows)
    attn = 4 * 32 * 15 * 64 * (rows * ctx)      # scores + weighted sum
    assert flops == pytest.approx(2 * n * rows + attn)
    assert flops == pytest.approx(23_156_490_240 + 3_932_160_000)
    # Every weight once (bf16) plus the live K/V of every row.
    assert nbytes == pytest.approx(2 * n + rows * ctx * 40_960)
    assert nbytes == pytest.approx(723_640_320 + 1_310_720_000)


def test_prefill_counts_causal_attention(smollm):
    # Positions 10..13 after 10 cached: contexts 11, 12, 13, 14.
    n = dense.dense_params(smollm)
    want = 2 * n * 4 + 4 * 32 * 15 * 64 * (11 + 12 + 13 + 14)
    assert dense.prefill_flops(smollm, 10, 14) == pytest.approx(want)
    assert dense.token_flops(smollm, 11) == pytest.approx(
        2 * n + 4 * 32 * 15 * 64 * 11)
