"""Block ``falcon_h1`` and its cell: the counts against hand-worked values at
Falcon-H1-34B-Instruct's widths, the block's leaves as the program's
``model.init`` has them, and the comparison that decides ``correct`` passing
the program and failing what it should: the reference with int4 weights, and
three faults planted in the program underneath the cell's toy engine
(``ssm_out_multiplier`` left out; the gate applied after the grouped norm;
decode starting from a zero state, the prefilled state and convolution tail
not spliced into the slot).

On the chip the same faults run at the cell's own size:

    python3 benchmark/tests/test_falcon_h1.py --fault ssm_out|gate|zero_state --seed <n> [--seconds 8]

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

CELL = "serve-falcon-h1-34b-reasoning"
CONFIG = "falcon-h1-34b-instruct-6of72"
BLOCK = harness.Block("falcon_h1")
ref = BLOCK.reference
FAULTS = ("ssm_out", "gate", "zero_state")


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
    """The program with a fault in it: ``ssm_out`` builds the model with
    ``ssm_out_multiplier`` left out (1.0); ``gate`` applies the gate after
    the grouped norm; ``zero_state`` splices K and V into a slot and leaves
    the prefilled state and convolution tail behind."""
    if fault == "ssm_out":
        owner, name = program.module("models"), "TransformerConfig"
        sound = getattr(owner, name)

        def broken(**kw):
            return sound(**{**kw, "ssm_out_multiplier": 1.0})

        broken.__dataclass_fields__ = sound.__dataclass_fields__
    elif fault == "gate":
        owner, name = program.module("models.mamba2"), "gate_and_norm"
        sound = getattr(owner, name)

        def broken(y, z, weight, groups, eps):
            grouped = y.reshape(*y.shape[:-1], groups, -1)
            normed = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
            return normed.reshape(y.shape) * weight * jax.nn.silu(z)
    else:
        owner, name = program.module("serve.engine"), "write_slot"
        sound = getattr(owner, name)

        def broken(cache, prefill_cache, *args, **kw):
            def wipe(path, leaf):
                forgot = str(path[-1].key) in ("ssm_state", "conv_state")
                return jnp.zeros_like(leaf) if forgot else leaf

            return sound(cache, jax.tree_util.tree_map_with_path(
                wipe, prefill_cache), *args, **kw)
    setattr(owner, name, broken)
    try:
        yield
    finally:
        setattr(owner, name, sound)


def args(seed=21, seconds=1.0, rehearse=True):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=seconds, trace=0, rehearse=rehearse,
        root=harness.ROOT, dump_trace=None, mix=[],
        t_process_start=time.perf_counter())


def test_parameters_by_hand():
    s = ref.Shape.from_config(config_of())
    p = ref.matmul_params(s)
    # q 5120 x 2560, k and v 5120 x 512, o 2560 x 5120
    assert p["attention"] == 5120 * (2560 + 512 + 512) + 2560 * 5120 == 31_457_280
    # in 5120 x (4096 + 5120 + 32), out 4096 x 5120
    assert s.conv_dim == 5120 and s.in_proj_width == 9248
    assert p["mamba"] == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert p["mlp"] == 3 * 5120 * 21504 == 330_301_440
    assert p["head"] == p["embedding"] == 5120 * 261_120
    # taps and bias 5 x 5120, dt_bias, A_log and D 3 x 32, w_norm 4096, two norms
    small = 5 * 5120 + 96 + 4096 + 2 * 5120
    assert ref.total_params(s) == 6 * (
        p["attention"] + p["mamba"] + p["mlp"] + small) + 2 * p["head"] + 5120
    assert ref.total_params(s) == weights.n_params(ref.leaf_shapes(s))
    assert round(ref.total_params(s) / 1e9, 2) == 5.25
    whole = ref.Shape.from_config(config_of(num_hidden_layers=72))
    assert round(ref.total_params(whole) / 1e9, 1) == 33.6  # the published "34B"


def test_serve_flops_by_hand():
    s = ref.Shape.from_config(config_of())
    p = ref.matmul_params(s)
    a_layer = p["attention"] + p["mamba"] + p["mlp"]
    # 3 prompt tokens, 2 generated: 4 positions through the 6 layers, each a
    # state step of 6 x 32 x 128 x 256 and a convolution of 2 x 4 x 5120;
    # position t meets t + 1 keys: 10 meetings of 4 x 20 x 128; the head twice
    state = 6 * 32 * 128 * 256 + 2 * 4 * 5120
    assert ref.serve_flops(s, 3, 2) == (
        6 * ((2 * a_layer + state) * 4 + 4 * 20 * 128 * 10) + 2 * p["head"] * 2)
    # a 1,024-token answer to a 900-token prompt: 12.7 TFLOP
    assert 12.5e12 < ref.serve_flops(s, 900, 1024) < 12.9e12


def test_leaves_mapped_are_the_programs_init():
    config = config_of(rehearse=True)
    shape = ref.Shape.from_config(config)
    for dtype in ("int8", "float32"):
        config["serve"]["weights_dtype"] = dtype
        model = BLOCK.program.model(config, "serve", config["serve"]["window"])
        ours = jax.eval_shape(lambda k: BLOCK.program.to_program(
            weights.build(ref.leaf_shapes(shape), k, dtype, 0.02), shape),
            jax.random.PRNGKey(0))
        theirs = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        program.check_same_structure(ours, theirs)
    # the published multipliers are the model's own: the mapping scales nothing
    assert model.cfg.ssm_multipliers == tuple(config["ssm_multipliers"])
    assert model.cfg.lm_head_multiplier == config["lm_head_multiplier"]
    assert model.cfg.head_dim == config["head_dim"] != (
        config["hidden_size"] // config["num_attention_heads"])


@pytest.mark.parametrize("std", [0.02, 1.0])
def test_drawn_parameters(std):
    """What the seed's ``N(0, std)`` draws become, whatever ``std``: taps at
    a deviation of 0.5, decays ``exp(A_log)`` spread over 1..16 a head,
    steps ``softplus(dt_bias)`` inside 1e-3..0.1 but for their jitter; a
    matrix and a norm as they were drawn."""
    shape = ref.Shape.from_config(config_of())
    small = {k: v for k, v in ref.leaf_shapes(shape)["layers"].items()
             if k in ("conv_weight", "a_log", "dt_bias", "d_skip", "ssm_norm")}
    tree = weights.make({"layers": small}, 3, "int8", std)
    got = ref.drawn(tree)["layers"]
    assert 0.49 < float(jnp.std(got["conv_weight"])) < 0.51
    decay = jnp.exp(got["a_log"])
    assert 0.9 < float(decay.min()) < 1.1 and 15 < float(decay.max()) < 17
    step = jax.nn.softplus(got["dt_bias"])
    assert 2e-4 < float(step.min()) and float(step.max()) < 0.5
    assert 0.005 < float(jnp.median(step)) < 0.02
    assert got["d_skip"] is tree["layers"]["d_skip"]


def _gaps(seed, fault=None, control=False):
    """The comparison that decides ``correct`` on a FIXED set of requests
    (eight prompts of 12-54 tokens, 10 tokens each, through the cell's toy
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


@pytest.mark.parametrize("seed", [21, 4])
def test_program_passes_and_control_and_faults_fail(seed):
    """At the rehearsal's limit: the program's every request under it; the
    reference with int4 weights and each planted fault over it by their
    widest gap."""
    sound, limit = _gaps(seed)
    assert max(sound) <= limit, sound
    for name, gaps in [("int4", _gaps(seed, control=True)[0])] + [
            (fault, _gaps(seed, fault)[0]) for fault in FAULTS]:
        assert max(gaps) > limit, (name, gaps, sound)


def test_a_whole_rehearsal_is_correct_and_a_planted_fault_runs():
    """The cell's whole run at the toy size is ``correct``; with a fault
    planted it still runs to its end (what it reads then is
    :func:`test_program_passes_and_control_and_faults_fail`'s)."""
    from benchmark import run

    line = run.run_cell(args(22))
    assert line["attempted"] > 0 and line["failed"] == 0 and line["correct"]
    with planted("zero_state"):
        line = run.run_cell(args(22))
    assert line["attempted"] > 0 and line["failed"] == 0


def _read(metric):
    return harness.load_module(os.path.join(
        harness.BENCH, "layer_metrics", metric + ".py")).read


def test_ssd_update_cost_and_roofline_by_hand(monkeypatch):
    """64 slots through one layer: 6 x 32 x 128 x 256 operations a slot;
    the state once in and once out, ``x``, ``y``, ``dt``, ``B`` and ``C``,
    in float32; the bytes bound it. The reader takes the sizes from the
    call's own operands."""
    from types import SimpleNamespace as NS

    from benchmark.lib import xplane

    cost = harness.kernel_cost(harness.ROOT, "ssd_update").cost
    ops, byts = cost(64, 32, 128, 256, groups=2)
    assert ops == 64 * 6 * 32 * 128 * 256
    assert byts == 64 * (2 * 32 * 256 * 128 + 2 * 32 * 128 + 32 + 2 * 2 * 256) * 4
    assert 536e6 < 64 * 2 * 32 * 256 * 128 * 4 < byts < 540e6
    cell = harness.Cell(CELL)
    peaks = cell.peaks["devices"]["TPU v5 lite"]
    assert byts / peaks["hbm_bytes_per_s"] > ops / peaks["flops_per_s"]["bfloat16"]
    calls = [
        NS(event=NS(seconds=1e-3), instruction="%ssd_update.7", operands=[
            ("s32", (1,)), ("s32", (64,)), ("s32", (64,)), ("s32", (64,)),
            ("f32", (64, 32, 128)), ("f32", (64, 32, 128)),
            ("f32", (64, 2, 256, 1)), ("f32", (64, 2, 256, 1)),
            ("f32", (6, 64, 32, 256, 128))]),
        NS(event=NS(seconds=1.0), instruction="%int8_matmul.9", operands=[
            ("f32", (64, 5120)), ("s8", (5120, 261120)), ("f32", (1, 261120))]),
    ]
    monkeypatch.setattr(xplane, "custom_calls", lambda events, lo, hi: calls)
    bundle = {"trace": NS(devices=[[]]), "busiest": 0, "trace_window": (0, 1),
              "peaks": peaks, "root": harness.ROOT}
    read = _read("ssd_update_roofline.serve")
    assert read(bundle) == pytest.approx(100 * byts / 819e9 / 1e-3, rel=1e-6)
    assert read(bundle) < 100
    calls[:] = calls[1:]  # a program without the kernel: the parent's
    assert read(bundle) is None
    assert read({"trace": None, "peaks": peaks}) is None


def test_ssd_update_share_reads_the_kernels_name(monkeypatch):
    """The kernel's ``name=`` is the innermost scope of its event's path;
    nothing to read (the parent's trace) leaves the metric out."""
    from benchmark.lib import program_trace, scope_share

    path = ("jit(_chain_fn)/while/body/closed_call/TransformerLM/layer_scan/"
            "while/body/closed_call/layers/block/mamba/ssm_scan/ssd_update/"
            "pallas_call")
    assert "ssd_update" in program_trace.scopes_on(path)
    assert "ssd_update" not in program_trace.scopes_on(
        path.replace("/ssd_update/pallas_call", "/mul"))
    seen = {"ssd_update": 29.0}
    monkeypatch.setattr(scope_share, "under", lambda bundle, s: seen.get(s))
    assert _read("ssd_update_share.serve")({}) == 29.0
    seen.clear()
    assert _read("ssd_update_share.serve")({}) is None


def test_cell_is_declared():
    spec = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reasoning", 1)
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == config_of()["reduced"]
    # every published key of the catalog's row, letter for letter
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Falcon-H1-34B-Instruct"' in line)
        ours = config_of()
        assert entry["source"] == row["source_url"] == ours["source"]
        assert {k: v for k, v in row["config"].items() if ours[k] != v} == {
            "num_hidden_layers": 72}
    mine = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert {"tpot_mean_ms", "setup_s", "slot_occupancy.serve", "mfu.serve",
            "int8_matmul_roofline", "device_idle.serve",
            "completed_tokens_per_s.serve", "chain_period.serve",
            "step_host.serve", "prefill_share.serve", "ssm_share.serve",
            "layer_scan_share.serve", "kv_cache_share.serve",
            "decode_attention_share.serve", "ssd_update_share.serve",
            "ssd_update_roofline.serve"} <= mine
    assert "int8_matmul_stacked_roofline.serve" not in mine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=FAULTS)
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
