"""Block ``sambay`` and its cell: the counts against hand-worked values at
Phi-4-mini-flash-reasoning's widths, the block's leaves as the program's
``model.init`` has them, and the comparison that decides ``correct`` passing
the program and failing what it should: the reference with int4 weights, and
two faults planted in the program underneath a whole rehearsal run (the
attention window one row short; ``m`` handed to the Gated Memory Units after
layer ``half``'s gate and not before it).

On the chip the same faults run at the cell's own size:

    python3 benchmark/tests/test_sambay.py --fault window|memory --seed <n> [--seconds 8]

prints the run's result line (``correct`` has to be false).
"""

import argparse
import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

if __name__ == "__main__":  # run as a script: no conftest has set the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.lib import harness, program, serve_kind, weights

CELL = "serve-phi-4-mini-flash-reasoning"
CONFIG = "phi-4-mini-flash-reasoning"
BLOCK = harness.Block("sambay")
ref = BLOCK.reference


def config_of(rehearse=False, **over):
    config = harness.read_json(
        os.path.join(harness.BENCH, "configs", CONFIG + ".json"))
    if rehearse:
        for k, v in config["rehearse"].items():
            config[k] = {**config[k], **v} if isinstance(v, dict) else v
    config.update(over)
    return config


@contextlib.contextmanager
def planted(fault: str):
    """The program with a fault in it: ``window`` makes every window
    layer's ring, and with it its mask, one row short; ``memory`` hands the
    Gated Memory Units layer ``half``'s output after its gate."""
    sambay = program.module("models.sambay")
    name, broken = {
        "window": ("ring_rows", lambda cfg: min(
            cfg.sliding_window, cfg.max_seq_len) - 1),
        "memory": ("handed_on", lambda y, gated: gated),
    }[fault]
    sound = getattr(sambay, name)
    setattr(sambay, name, broken)
    try:
        yield
    finally:
        setattr(sambay, name, sound)


def args(seed=21, seconds=1.0, rehearse=True):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=seconds, trace=0, rehearse=rehearse,
        root=harness.ROOT, dump_trace=None, mix=[],
        t_process_start=time.perf_counter())


def test_parameters_by_hand():
    s = ref.Shape.from_config(config_of())
    p = ref.matmul_params(s)
    assert p["mlp"] == 3 * 2560 * 10240 == 78_643_200
    # W_in 2560 x 10240, W_x 5120 x 192, W_dt 160 x 5120, W_out 5120 x 2560
    assert p["mamba"] == 26_214_400 + 983_040 + 819_200 + 13_107_200
    assert p["own"] == 2560 * 5120 + 2560 * 2560 == 19_660_800
    assert p["cross"] == 2 * 2560 * 2560 and p["gmu"] == 2 * 2560 * 5120
    assert p["kv"] == 2560 * 2560  # 20 KV heads of 64, K and V
    assert p["head"] == 2560 * 200_064
    assert ref.layer_counts(s) == {"mamba": 9, "own": 9, "gmu": 7, "cross": 7}
    assert [ref.kind_of(s, l) for l in (0, 15, 16, 17, 18, 31)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    # taps, two biases, A_log and D a Mamba mixer: 41.24 M with its matrices
    assert round((p["mamba"] + 4 * 5120 + 3 * 5120 + 16 * 5120) / 1e6, 2) == 41.24
    assert ref.total_params(s) == weights.n_params(ref.leaf_shapes(s))
    assert round(ref.total_params(s) / 1e9, 3) == 3.853  # the published "3.8B"


def test_serve_flops_by_hand():
    s = ref.Shape.from_config(config_of())
    p = ref.matmul_params(s)
    early = 9 * (p["mamba"] + p["mlp"]) + 8 * (p["own"] + p["mlp"])
    late = p["own"] + p["mlp"] + 7 * (p["gmu"] + p["mlp"]) + 7 * (p["cross"] + p["mlp"])
    # 3 prompt tokens, 2 generated: 4 positions through layers 0-16, K and V
    # of 2 prompt positions more, layers 17-31 twice, the head twice
    matrices = 2 * early * 4 + 2 * p["kv"] * 2 + 2 * late * 2 + 2 * p["head"] * 2
    scan = 9 * (6 * 5120 * 16 + 2 * 4 * 5120) * 4
    # a query meets a key: 20 pairs x (2 x 64 + 2 x 128) multiply-adds;
    # 4 positions see 1 + 2 + 3 + 4 keys in each of 8 window layers; the
    # shared cache: the prompt's last position 3 keys, the token after it
    # 4, in layer 17 and the 7 cross layers
    meet = 2 * 20 * (2 * 64 + 2 * 128)
    assert ref.serve_flops(s, 3, 2) == matrices + scan + meet * (8 * 10 + 8 * 7)
    # past the window a position sees 512 keys, not all before it
    long = ref.serve_flops(s, 1000, 2) - ref.serve_flops(s, 999, 2)
    assert long == pytest.approx(
        2 * early + 2 * p["kv"] + scan / 4 + meet * (8 * 512 + 8 * 2), rel=1e-9)
    # a 1,024-token answer to a 900-token prompt: 11.5 TFLOP
    assert 11.3e12 < ref.serve_flops(s, 900, 1024) < 11.8e12


def test_leaves_mapped_are_the_programs_init():
    config = config_of(rehearse=True)
    shape = ref.Shape.from_config(config)
    model = BLOCK.program.model(config, "serve", config["serve"]["window"])
    for dtype in ("int8", "float32"):
        config["serve"]["weights_dtype"] = dtype
        model = BLOCK.program.model(config, "serve", config["serve"]["window"])
        ours = jax.eval_shape(lambda k: BLOCK.program.to_program(
            weights.build(ref.leaf_shapes(shape), k, dtype, 0.02), shape),
            jax.random.PRNGKey(0))
        theirs = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        program.check_same_structure(ours, theirs)


def test_drawn_parameters():
    """What the seed's ``N(0, 0.02)`` draws become: taps at 0.5, lambda
    vectors at 0.1, ``A_log`` around ``log(n + 1)``, ``b_dt`` around
    ``softplus^-1(0.01)``; a matrix and a norm as they were drawn."""
    shape = ref.Shape.from_config(config_of(rehearse=True))
    tree = weights.make(ref.leaf_shapes(shape), 3, "int8", 0.02)
    got = ref.drawn(tree)
    mixer = got["layers_a"]["mamba"]
    assert 0.4 < float(jnp.std(mixer["conv_weight"])) < 0.6
    assert 0.08 < float(jnp.std(got["mid_full"]["lambda_q1"])) < 0.13
    assert jnp.allclose(jnp.mean(mixer["a_log"], (0, 2)),
                        jnp.log(jnp.arange(1, shape.mamba_d_state + 1)), atol=0.01)
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 0.005 < float(jnp.median(dt)) < 0.02
    assert mixer["in_proj"]["q"] is tree["layers_a"]["mamba"]["in_proj"]["q"]
    assert got["final_norm"]["scale"] is tree["final_norm"]["scale"]


def _gaps(seed, fault=None, control=False):
    """The comparison that decides ``correct`` on a FIXED set of requests
    (eight prompts of 12-60 tokens, 10 tokens each, through the cell's toy
    engine, drained): a whole rehearsal run samples the requests its one
    second happened to finish, and its reading moves with the machine's
    load. Returns each request's widest gap."""
    from benchmark.lib import traffic

    cell = harness.Cell(CELL)
    harness.rehearsal_sizes(cell)
    with planted(fault) if fault else contextlib.nullcontext():
        shape, ref_params, engine = serve_kind.build_engine(cell, seed)
        prompts = [
            traffic.prompt_tokens(seed, i, 12 + 6 * i, shape.vocab_size)
            for i in range(8)
        ]
        ids = [engine.submit(program.request(p, 10)) for p in prompts]
        done = {c.request_id: c for c in engine.run_until_idle()}
    served = [(list(done[i].prompt), list(done[i].tokens)) for i in ids]
    gaps, compared = serve_kind.token_gaps(
        cell.block, shape, ref_params, served, cell.config["serve"]["window"],
        weight_bits=4 if control else 8)
    assert compared == 80
    return gaps, cell.limit("served_token_gap")


@pytest.mark.parametrize("seed", [21, 22])
def test_program_passes_and_control_and_faults_fail(seed):
    """At the rehearsal's limit: the program's every request under it; the
    reference with int4 weights, the window one row short (ring 8 -> 7)
    and ``m`` taken after the gate each over it by their widest gap."""
    sound, limit = _gaps(seed)
    assert max(sound) <= limit, sound
    for name, gaps in (("int4", _gaps(seed, control=True)[0]),
                       ("window", _gaps(seed, "window")[0]),
                       ("memory", _gaps(seed, "memory")[0])):
        assert max(gaps) > limit, (name, gaps, sound)


def test_a_whole_rehearsal_is_correct_and_a_planted_fault_runs():
    """The cell's whole run at the toy size is ``correct``; with a fault
    planted it still runs to its end (what it reads then is
    :func:`test_program_passes_and_control_and_faults_fail`'s)."""
    from benchmark import run

    line = run.run_cell(args(22))
    assert line["attempted"] > 0 and line["failed"] == 0 and line["correct"]
    with planted("memory"):
        line = run.run_cell(args(22))
    assert line["attempted"] > 0 and line["failed"] == 0


def test_new_shares_read_their_scopes(monkeypatch):
    from benchmark.lib import scope_share

    seen = {"ssm_conv": 0.5, "ssm_scan": 3.5, "shared_kv_attn": 30.0,
            "window_attn": 9.0, "gmu": 1.5}
    monkeypatch.setattr(scope_share, "under", lambda bundle, s: seen.get(s))

    def read(metric):
        return harness.load_module(os.path.join(
            harness.BENCH, "layer_metrics", metric + ".py")).read({})

    assert read("ssm_share.serve") == 4.0
    assert read("shared_kv_attention_share.serve") == 30.0
    assert read("window_attention_share.serve") == 9.0
    assert read("gmu_share.serve") == 1.5
    seen.clear()  # a program without the scopes: the parent's
    assert [read(m + ".serve") for m in (
        "ssm_share", "shared_kv_attention_share", "window_attention_share",
        "gmu_share")] == [None] * 4


def test_selective_scan_cost_and_roofline_by_hand(monkeypatch):
    """A prompt bucket of 2,048 through one layer: 6 x 5,120 x 16 operations
    a position; ``u``, ``delta``, ``y``, ``B`` and ``C`` a position and
    ``A`` and the last state once, in float32; the bytes bound it."""
    from types import SimpleNamespace as NS

    from benchmark.lib import xplane

    cost = harness.kernel_cost(harness.ROOT, "selective_scan").cost
    ops, byts = cost(1, 2048, 5120, 16)
    assert ops == 6 * 2048 * 5120 * 16
    assert byts == 2048 * (3 * 5120 + 2 * 16) * 4 + 2 * 16 * 5120 * 4
    cell = harness.Cell(CELL)
    peaks = cell.peaks["devices"]["TPU v5 lite"]
    assert byts / peaks["hbm_bytes_per_s"] > ops / peaks["flops_per_s"]["bfloat16"]
    calls = [
        NS(event=NS(seconds=2e-3), instruction="%selective_scan.3", operands=[
            ("f32", (1, 2048, 5120)), ("f32", (1, 2048, 5120)), ("f32", (16, 5120)),
            ("f32", (1, 2048, 16, 1)), ("f32", (1, 2048, 16, 1))]),
        NS(event=NS(seconds=1.0), instruction="%int8_matmul.9", operands=[
            ("f32", (64, 2560)), ("s8", (2560, 5120)), ("f32", (1, 5120))]),
    ]
    monkeypatch.setattr(xplane, "custom_calls", lambda events, lo, hi: calls)
    bundle = {"trace": NS(devices=[[]]), "busiest": 0, "trace_window": (0, 1),
              "peaks": peaks, "root": harness.ROOT}
    read = harness.load_module(os.path.join(
        harness.BENCH, "layer_metrics", "selective_scan_roofline.serve.py")).read
    assert read(bundle) == pytest.approx(100 * byts / 819e9 / 2e-3, rel=1e-6)
    calls[:] = calls[1:]  # a program that scans with lax.scan: the parent's
    assert read(bundle) is None


def test_cell_is_declared():
    spec = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reasoning", 1)
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] == config_of()["reduced"]
    mine = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert {"tpot_mean_ms", "setup_s", "mfu.serve", "int8_matmul_roofline",
            "layer_scan_share.serve", "kv_cache_share.serve",
            "decode_attention_share.serve", "ssm_share.serve",
            "shared_kv_attention_share.serve", "window_attention_share.serve",
            "gmu_share.serve", "selective_scan_roofline.serve"} <= mine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=("window", "memory"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    opts = ap.parse_args()

    from benchmark import run

    with planted(opts.fault):
        line = run.run_cell(args(opts.seed, opts.seconds, rehearse=False))
    sys.stderr.flush()
    print(json.dumps(dict(line, fault=opts.fault)), flush=True)


if __name__ == "__main__":
    main()
