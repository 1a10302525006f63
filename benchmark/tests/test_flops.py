"""The analytic counts against hand-worked values for both models."""

import os

import pytest

from benchmark.lib import harness, weights

CONFIGS = os.path.join(harness.BENCH, "configs")
flops = harness.Block("gqa_swiglu").reference  # the block's own counts


def shape_of(name):
    return flops.Shape.from_config(
        harness.read_json(os.path.join(CONFIGS, name + ".json")))


def test_internlm2_parameters():
    s = shape_of("internlm2-1.8b")
    p = flops.matmul_params(s)
    # a layer: 2048 x (2048 + 2 x 1024) + 2048 x 2048 + 3 x 2048 x 8192
    assert p["layer"] == 8_388_608 + 4_194_304 + 50_331_648 == 62_914_560
    assert p["head"] == p["embedding"] == 2048 * 92544 == 189_530_112
    assert flops.total_params(s) == 24 * 62_914_560 + 2 * 189_530_112 + 2048 * 49
    assert round(flops.total_params(s) / 1e9, 3) == 1.889
    assert weights.n_params(flops.leaf_shapes(s)) == flops.total_params(s)


def test_mistral_parameters():
    s = shape_of("mistral-7b-v0.1")
    p = flops.matmul_params(s)
    # 4096 x (4096 + 2 x 1024) + 4096 x 4096 + 3 x 4096 x 14336
    assert p["layer"] == 25_165_824 + 16_777_216 + 176_160_768 == 218_103_808
    assert round(flops.total_params(s) / 1e9, 2) == 7.24
    assert weights.n_params(flops.leaf_shapes(s)) == flops.total_params(s)


def test_train_flops_of_the_one_chip_cell():
    s = shape_of("internlm2-1.8b-4of24")
    per_token = flops.train_flops_per_token(s, 2048)
    # 6 x (4 x 62.9 M + 189.5 M) + 12 x 4 x 16 x 128 x 2048
    assert per_token == 6 * (4 * 62_914_560 + 189_530_112) + 201_326_592
    assert round(per_token * 8192 / 1e12, 1) == 23.3  # a step
    head = 6 * 189_530_112 / per_token
    assert 0.39 < head < 0.41  # the head's share the configuration states


def test_serve_flops_by_hand():
    s = shape_of("mistral-7b-v0.1")
    # 3 prompt tokens, 2 generated: 4 tokens through the layers (positions
    # 1..4 of causal attention: 10 key-query pairs), the head twice
    want = (2 * 32 * 218_103_808 * 4 + 2 * 4096 * 32000 * 2
            + 4 * 32 * 32 * 128 * 10)
    assert flops.serve_flops(s, 3, 2) == want
    # a 2048-token prompt is about 30 TFLOP
    assert 28e12 < flops.serve_flops(s, 2048, 1) < 31e12


@pytest.mark.parametrize("kernel,args,ops,byts", [
    ("flash_attention", dict(batch=1, heads=2, kv_heads=1, seq=8, head_dim=4, itemsize=2),
     2 * 2 * 2 * 8 * 8 / 2 * 4, 2 * 2 * 8 * 4 * 2 + 2 * 1 * 8 * 4 * 2),
    ("fused_loss", dict(rows=8, d=4, vocab=16, itemsize=2), 2 * 8 * 4 * 16,
     (8 * 4 + 4 * 16) * 2 + 8 * 4),
    ("int8_matmul", dict(m=8, k=4, n=16), 2 * 8 * 4 * 16,
     8 * 4 * 4 + 4 * 16 + 4 * 16 + 8 * 16 * 4),
])
def test_kernel_costs_by_hand(kernel, args, ops, byts):
    assert harness.kernel_cost(harness.ROOT, kernel).cost(**args) == (ops, byts)


def test_peaks_table_names_its_source():
    peaks = harness.read_json(os.path.join(harness.BENCH, "peaks.json"))
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == {"bfloat16": 197e12, "int8": 393e12}
    assert v5e["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in peaks["source"]
