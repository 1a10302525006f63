"""The seam between the harness and a block (``benchmark/blocks/<block>/``):
every configuration resolves to a block whose files load and whose leaves
are the program's, the first block's weights are bit for bit what they were
before the seam, and only the two program halves import the program."""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, program, weights

SPEC = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))

# sha256 over every leaf's path, type, shape and bytes of what PR 28's
# ``weights.make`` gave at internlm2-1.8b-4of24's rehearsal widths
PARENT_WEIGHTS = {
    (0, "float32"): "9c35e6b988c3cef73d548204549f84cb53c97cfcbc2cf8fbd0d11fc5b291b62d",
    (0, "int8"): "961dff37dcd9387dbafc6e80c0d02fe913dc1384a727b1fb51eb818eb10839e4",
    (2**31 + 5, "float32"): "fa8b86ab47cf2d46fccb7e25fb20a13f88e44fcc2eac85bfb126b3135db513fa",
    (2**31 + 5, "int8"): "43f427b8adfd7453c71219b0426e709f7a76ad14e4696bc46a3e45678d19a9c8",
}


def rehearsal_config(entry):
    config = harness.read_json(os.path.join(harness.ROOT, entry["file"]))
    for k, v in config.pop("rehearse", {}).items():  # as rehearsal_sizes does
        config[k] = {**config[k], **v} if isinstance(v, dict) else v
    return config, harness.Block(config.get("block", harness.DEFAULT_BLOCK))


def digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_WEIGHTS))
def test_first_block_weights_are_the_parents(seed, dtype):
    entry = next(c for c in SPEC["configs"] if c["name"] == "internlm2-1.8b-4of24")
    config, block = rehearsal_config(entry)
    assert block.name == "gqa_swiglu"
    spec = block.reference.leaf_shapes(block.reference.Shape.from_config(config))
    tree = weights.make(spec, seed, dtype, config["initializer_range"])
    assert digest(tree) == PARENT_WEIGHTS[seed, dtype]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_resolves_to_a_block_with_the_programs_leaves(entry):
    config, block = rehearsal_config(entry)
    ref = block.reference
    shape = ref.Shape.from_config(config)
    assert shape.vocab_size == config["vocab_size"]
    assert set(ref.MODES) <= {"train", "serve"} and ref.MODES
    spec = ref.leaf_shapes(shape)
    assert weights.n_params(spec) == ref.total_params(shape)
    for mode in ref.MODES:
        if mode not in config:
            continue
        dtype = config[mode].get("weights_dtype", "float32")
        ours = jax.eval_shape(lambda k: block.program.to_program(
            weights.build(spec, k, dtype, 0.02), shape), jax.random.PRNGKey(0))
        model = block.program.model(config, mode, 64)
        theirs = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        program.check_same_structure(ours, theirs["params"])
        if mode == "train":  # and back, in the reference's own layout
            back = jax.eval_shape(block.program.from_program, theirs["params"])
            want = jax.eval_shape(
                lambda k: weights.build(spec, k, "float32", 0.02),
                jax.random.PRNGKey(0))
            program.check_same_structure(back, want)
        if mode == "train":
            assert callable(ref.grad_fn) and ref.train_flops_per_token(shape, 32) > 0
        else:
            assert callable(ref.logits) and ref.serve_flops(shape, 8, 4) > 0


def test_a_cell_of_a_mode_its_block_lacks_exits(monkeypatch):
    block = harness.Block("gqa_swiglu")
    monkeypatch.setattr(block.reference, "MODES", ("serve",))
    with pytest.raises(SystemExit, match="has no 'train' mode"):
        block.needs("train")
    with pytest.raises(SystemExit, match="no block 'nowhere'"):
        harness.Block("nowhere")


def test_only_the_program_halves_import_the_program():
    # the package's name and the import machinery appear in lib/program.py
    # alone; a block's half reaches the program through its ``module()``
    # and nobody else calls that
    direct = re.compile(program.PKG + r"|import_module\(|__import__\(")
    through = re.compile(r"\bmodule\(")
    importers, users = set(), set()
    for base, _, files in os.walk(harness.BENCH):
        if "__pycache__" in base or os.sep + "tests" in base:
            continue
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    text = fh.read()
                rel = os.path.relpath(path, harness.BENCH)
                if direct.search(text):
                    importers.add(rel)
                if through.search(text):
                    users.add(rel)
    shared = os.path.join("lib", "program.py")
    halves = {os.path.join("blocks", b, "program.py")
              for b in os.listdir(os.path.join(harness.BENCH, "blocks"))
              if os.path.isfile(os.path.join(harness.BENCH, "blocks", b, "program.py"))}
    assert importers == {shared}
    assert halves and users == {shared} | halves
