"""Block ``mla_moe`` and its cell: the analytic counts against hand-worked
values at openPangu-Ultra-MoE's widths, the new kernels' costs, and the
comparison that decides ``correct`` failing what it should: the reference
with int4 weights, and the program routing one expert a token fewer."""

import json
import os

import pytest

from benchmark.lib import harness, weights
from benchmark.tests import later_cell
from benchmark.tests.test_add_files import rehearse
from benchmark.tests.test_control import args

CELL = "serve-openpangu-ultra-moe-reasoning"
CONFIG = "openpangu-ultra-moe-718b-ep16-7of61"
ref = harness.Block("mla_moe").reference


def shape_of(**over):
    config = harness.read_json(
        os.path.join(harness.BENCH, "configs", CONFIG + ".json"))
    config.update(over)
    return ref.Shape.from_config(config)


def test_parameters_by_hand():
    s = shape_of()
    p = ref.matmul_params(s)
    # W_dq 7680 x 1536, W_uq 1536 x 128 x 192, W_dkv 7680 x 576, W_o 16384 x 7680
    assert p["attention"] == 11_796_480 + 37_748_736 + 4_423_680 + 125_829_120
    assert p["w_ukv"] == 512 * 128 * 256 == 16_777_216
    assert p["attention"] + p["w_ukv"] == 196_575_232  # 196.6 M a layer
    assert p["dense_ffn"] == 3 * 7680 * 18432 == 424_673_280
    assert p["expert"] == p["shared"] == 3 * 7680 * 2048 == 47_185_920
    assert p["router"] == 7680 * 256
    assert p["head"] == 7680 * 19200 == 147_456_000
    norms = 7 * (4 * 7680 + 1536 + 512) + 7680
    assert ref.total_params(s) == (
        7 * 196_575_232 + 424_673_280
        + 6 * (47_185_920 + 1_966_080 + 16 * 47_185_920)
        + 2 * 147_456_000 + norms)
    assert round(ref.total_params(s) / 1e9, 2) == 6.92
    assert weights.n_params(ref.leaf_shapes(s)) == ref.total_params(s)


def test_serve_flops_by_hand():
    s = shape_of()
    # 3 prompt tokens, 2 generated: 4 tokens through the layers, the head
    # twice. A token meets, a layer, the attention matrices; the dense
    # feed-forward in 1 layer; in 6 the shared expert, the router and
    # 8 x 16 / 256 = 0.5 routed experts.
    a_token = (7 * 179_798_016 + 424_673_280
               + 6 * (47_185_920 + 1_966_080 + 0.5 * 47_185_920))
    matrices = 2 * a_token * 4 + 2 * 147_456_000 * 2
    # the prompt: W_ukv on 3 positions, 6 query-key pairs over 192 + 128
    prefill = 2 * 16_777_216 * 3 + 2 * 128 * 320 * 6
    # the one decoded token through the layers: W_ukv's operations once
    # (absorb and un-absorb), 4 latents attended over 576 and 512
    decode = 2 * 16_777_216 * 1 + 2 * 128 * (576 + 512) * 4
    assert ref.serve_flops(s, 3, 2) == matrices + 7 * (prefill + decode)
    # a 1,024-token answer to a 900-token prompt is about 12 TFLOP, a
    # quarter of it the decoded tokens' attention over the latents
    assert 11.5e12 < ref.serve_flops(s, 900, 1024) < 12.5e12
    # all 256 experts held: 8 pairs a token
    whole = shape_of(n_routed_experts=256)
    more = ref.serve_flops(whole, 3, 2) - ref.serve_flops(s, 3, 2)
    assert more == 2 * 6 * 7.5 * 47_185_920 * 4


@pytest.mark.parametrize("kernel,args_,ops,byts", [
    ("grouped_int8_matmul", dict(pairs=64, experts=16, k=8, n=4),
     2 * 64 * 8 * 4, 16 * (8 * 4 + 4 * 4) + 64 * (8 * 2 + 4 * 2)),
    # a head-row over a cached token: scores on rank + rope, values on rank;
    # a token of cache is rank + rope numbers, whatever row it is stored in
    ("latent_decode_attention",
     dict(slots=2, heads=4, context=10, rank=6, rope=2),
     2 * 4 * 10 * (2 * 8 + 2 * 6), 2 * (10 * 8 * 2 + 4 * (8 * 2 + 6 * 4))),
    # the cell's own: 2 * (576 + 512) operations a head-row, 1,152 B a token
    ("latent_decode_attention",
     dict(slots=1, heads=128, context=1, rank=512, rope=64),
     128 * 2 * (576 + 512), 1152 + 128 * (1152 + 2048)),
])
def test_kernel_costs_by_hand(kernel, args_, ops, byts):
    assert harness.kernel_cost(harness.ROOT, kernel).cost(**args_) == (ops, byts)


def test_expected_pairs_from_the_grouped_kernels_shapes():
    kernel = harness.kernel_cost(harness.ROOT, "grouped_int8_matmul")
    # 64 slots x 8 choices in tiles of 16 rows, 16 held experts of 256:
    # 512 + 16 x 16 rows in 48 tiles, 32 pairs expected
    assert kernel.expected_pairs(768, 48, 16, 256) == 32.0
    # a 2,048-token prefill in tiles of 128 rows
    assert kernel.expected_pairs(16384 + 2048, 144, 16, 256) == 1024.0
    # 512 choices leave an expert without a pair 13.5 % of the time
    # ((255 / 256) ** 512): 13.8 of the 16 are read; a prefill reads all
    assert round(kernel.expected_experts(768, 48, 16, 256), 2) == 13.84
    assert round(kernel.expected_experts(16384 + 2048, 144, 16, 256), 6) == 16.0
    assert kernel.expected_experts(16 + 4 * 16, 5, 4, 4) == 4 * (1 - 0.75 ** 16)


def test_int4_control_stands_clear_of_the_program():
    from benchmark import run
    from benchmark.lib import serve_kind

    seen = {}

    def decide(cell, a, bundle, checks):
        proof = bundle["proof"]
        common = (bundle["block"], bundle["shape"], proof["ref_params"],
                  proof["served"], cell.config["serve"]["window"])
        seen["program"] = max(serve_kind.token_gaps(*common)[0])
        seen["control"] = max(serve_kind.token_gaps(*common, weight_bits=4)[0])
        seen["limit"] = cell.limit("served_token_gap")
        checks.at_most("placeholder", 0, 0)
        return {}

    run.run_cell(args(CELL), control=decide)
    assert seen["program"] <= seen["limit"] < seen["control"], seen


@pytest.mark.parametrize("fault", [None, "one_expert_a_token_fewer"])
def test_routing_one_expert_a_token_fewer_is_not_correct(fault, tmp_path):
    """7 experts a token in place of 8 at the cell's size; at the toy 1 in
    place of 2, planted in a copy of the block's program half."""
    root = str(tmp_path)
    spec = later_cell.copy_of_benchmark(root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    if fault:
        path = os.path.join(root, "benchmark", "blocks", "mla_moe", "program.py")
        with open(path) as f:
            text = f.read()
        good = 'experts_per_token=config["num_experts_per_tok"]'
        assert text.count(good) == 1
        with open(path, "w") as f:
            f.write(text.replace(good, good + " - 1"))
    line = rehearse(root, CELL)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is (fault is None), line["checks"]


@pytest.mark.parametrize("metric,want", [
    ("moe_share.serve", 1.5 + 4.0 + 20.0), ("moe_dispatch_share.serve", 1.5 + 4.0),
])
def test_expert_shares_add_their_scopes_up(metric, want, monkeypatch):
    from benchmark.lib import scope_share

    seen = {"moe_router": 1.5, "moe_dispatch": 4.0, "moe_experts": 20.0}
    monkeypatch.setattr(scope_share, "under", lambda bundle, s: seen.get(s))
    read = harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", metric + ".py")).read
    assert read({}) == want  # moe_shared has nothing under it here
    seen.clear()
    assert read({}) is None  # a program without the scopes: the parent's


def test_kernel_rooflines_from_a_trace_of_the_cells_decode_step(monkeypatch):
    """Both roofline readers on a made-up trace of one decode step of the
    cell (64 slots: a grouped product of 768 rows in 48 tiles that took 0.4
    ms, a latent attention call that took 0.5 ms), against hand counts."""
    from types import SimpleNamespace as NS

    from benchmark.lib import xplane

    cell = harness.Cell(CELL)
    peaks = cell.peaks["devices"]["TPU v5 lite"]
    calls = [
        NS(event=NS(seconds=4e-4), operands=[
            ("s32", (48,)), ("bf16", (768, 7680)), ("s8", (16, 7680, 2048)),
            ("f32", (16, 1, 2048))]),
        NS(event=NS(seconds=5e-4), operands=[
            ("s32", ()), ("s32", (64,)), ("bf16", (64, 128, 640)),
            ("bf16", (7, 64, 4096, 640))]),
    ]
    monkeypatch.setattr(xplane, "custom_calls", lambda events, lo, hi: calls)
    bundle = {
        "trace": NS(devices=[[]]), "busiest": 0, "trace_window": (0, 1),
        "peaks": peaks, "cell": cell, "root": harness.ROOT,
        "counters": {"done_lengths": [(1000, 1000), (500, 1000)]},
    }

    def read(metric):
        return harness.load_module(os.path.join(
            harness.BENCH, "layer_metrics", metric + ".py")).read(bundle)

    # 13.84 of 16 experts read (7680 x 2048 int8 + 2048 scales each), 32 pairs
    byts = 13.8439 * (7680 * 2048 + 4 * 2048) + 32 * (7680 + 2048) * 2
    assert read("grouped_int8_matmul_roofline.serve") == pytest.approx(
        100 * byts / 819e9 / 4e-4, rel=1e-4)
    # context 1,250 rows a slot of 1,152 B, and 128 head-rows of queries in
    # and of float32 results out: the bytes bound it, just over the
    # operations (2 x (576 + 512) a head-row and cached token)
    byts = 64 * (1250 * 1152 + 128 * (1152 + 4 * 512))
    assert byts / 819e9 > 64 * 128 * 1250 * 2 * (576 + 512) / 197e12
    assert read("latent_decode_attention_roofline.serve") == pytest.approx(
        100 * byts / 819e9 / 5e-4, rel=1e-4)
