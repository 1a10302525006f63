"""Sharded serving (ISSUE 15): ServeEngine over a TensorParallel model.

The load-bearing pins:

- ``strategy=TensorParallel(...)`` at tp=2 serves a head/FFN-sharded
  model TOKEN-EXACT vs the replicated engine and one-shot
  ``generate()`` — the slot machinery (refill DUS, bucketed prefill,
  chained decode) is invisible in the outputs while the KV cache is
  genuinely head-sharded on device (shard shapes prove it, not specs);
- a tp=1 / model-axis-free strategy is BYTE-IDENTICAL to the bare
  engine: same slot-state tree, same compiled-program counts — the
  ``_shard`` gate keeps the off path free of constraint ops;
- the fetch budget is UNCHANGED at every tp: one batched fetch per
  chain plus one scalar per prefill/splice, counted by monkeypatching
  ``jax.device_get`` — sharding must never add a host sync;
- NOTHING recompiles after warmup (``_cache_size()`` pins), and the
  compiled decode chain's HLO contains no collective beyond the
  Megatron all-reduces (``audit_decode_hlo`` — an all-gather /
  reduce-scatter in the decode program means a cache leaf got
  resharded, the exact copy SLOT_STATE_RULES exists to prevent);
- the contract generalizes: tp=4 and the scan_layers / GQA / int8-KV
  cache layouts (slow-marked), composed with prefix splices +
  speculation + adapters + paged KV + depth-2 pipelining, all stay
  engine-vs-engine token-exact.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_tutorials_tpu.models.generate import generate
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TP_RULES,
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.parallel import TensorParallel
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.serve import Request, ServeEngine

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=64
)


def _make(cfg=CFG, seed=0):
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def _prompt(seed, p_len, vocab=CFG.vocab_size):
    return jax.device_get(
        jax.random.randint(jax.random.PRNGKey(seed), (p_len,), 0, vocab)
    ).tolist()


def _reference(model, params, prompt, max_new):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32), max_new)
    return jax.device_get(out)[0, len(prompt):].tolist()


def _tp(n):
    return TensorParallel(create_mesh({"model": n}), TP_RULES)


def _run_stream(model, params, reqs, **engine_kwargs):
    """Staggered submit (2 up front, one per scheduling round after)."""
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, **engine_kwargs
    )
    ids = [
        engine.submit(Request(prompt=p, max_new_tokens=m, seed=i))
        for i, (p, m) in enumerate(reqs[:2])
    ]
    pending = list(range(2, len(reqs)))
    completions = {}
    while not engine.idle or pending:
        if pending:
            i = pending.pop(0)
            p, m = reqs[i]
            ids.append(engine.submit(Request(prompt=p, max_new_tokens=m,
                                             seed=i)))
        for c in engine.step():
            completions[c.request_id] = c
    return engine, [completions[rid] for rid in ids]


def _tree_identical(a, b):
    la, sa = jax.tree_util.tree_flatten(a)
    lb, sb = jax.tree_util.tree_flatten(b)
    return sa == sb and all(
        x.dtype == y.dtype and x.shape == y.shape and bool((x == y).all())
        for x, y in zip(la, lb)
    )


def _kv_leaf(engine, name="cached_key"):
    """First cache leaf whose path ends in ``name``."""
    for kp, leaf in jax.tree_util.tree_leaves_with_path(
        engine._state["cache"]
    ):
        if jax.tree_util.keystr(kp).endswith(f"['{name}']"):
            return leaf
    raise AssertionError(f"no {name} leaf in the slot cache")


@pytest.fixture(scope="module")
def model_params():
    return _make()


REQS = [(3, 9), (7, 12), (5, 5), (12, 6), (2, 17)]


# ----------------------------------------------------- off-path identity

def test_tp1_byte_identical_to_bare_engine(model_params):
    """A strategy whose mesh has NO model axis (tp_size == 1) gates the
    whole sharded path off: byte-identical slot-state tree, identical
    compiled-program counts, identical completions vs strategy=None —
    the same off-path discipline every serve feature keeps."""
    model, params = model_params
    reqs = [(_prompt(8000 + i, p), m) for i, (p, m) in enumerate(REQS[:3])]
    strat = TensorParallel(create_mesh({"data": 2}), TP_RULES)
    assert strat.tp_size == 1
    eng_b, out_b = _run_stream(model, params, reqs)
    eng_t, out_t = _run_stream(model, params, reqs, strategy=strat)
    assert eng_t._shard is False and eng_t.tp_stats() == {"tp": 1}
    assert [c.tokens for c in out_t] == [c.tokens for c in out_b]
    assert _tree_identical(eng_t._state, eng_b._state)
    assert eng_t._chain._cache_size() == eng_b._chain._cache_size()
    assert eng_t._prefill._cache_size() == eng_b._prefill._cache_size()


# ------------------------------------------------- the acceptance pin

def test_tp2_token_exact_and_kv_sharded(model_params):
    """tp=2 over the staggered mixed-length stream: every completion
    matches the replicated engine and one-shot generate() token for
    token, while the KV cache leaves are GENUINELY head-sharded on
    device (per-shard shapes halve the head dim) and tp_stats prices
    per-chip KV at half the global bytes."""
    from pytorch_distributed_training_tutorials_tpu.serve.slots import tree_nbytes

    model, params = model_params
    reqs = [(_prompt(8100 + i, p), m) for i, (p, m) in enumerate(REQS)]
    eng_r, out_r = _run_stream(model, params, reqs)
    eng_t, out_t = _run_stream(model, params, reqs, strategy=_tp(2))
    assert [c.tokens for c in out_t] == [c.tokens for c in out_r]
    for (p, m), c in zip(reqs, out_t):
        assert c.tokens == _reference(model, params, p, m)
        assert c.finish_reason == "length"
    kv = _kv_leaf(eng_t)
    assert kv.shape == (2, 64, 4, 8)
    assert {s.data.shape for s in kv.addressable_shards} == {(2, 64, 2, 8)}
    stats = eng_t.tp_stats()
    assert stats["tp"] == 2 and stats["mesh_shape"] == "model:2"
    glob = tree_nbytes(eng_t._state["cache"])
    assert stats["tp_kv_bytes_per_chip"] < glob
    # bookkeeping leaves stay replicated (whole-shape shards)
    idx = _kv_leaf(eng_t, "cache_index")
    assert {s.data.shape for s in idx.addressable_shards} == {idx.shape}


def test_tp2_at_head_dim_128_keeps_the_plain_decode_path(monkeypatch):
    """At real head widths (``head_dim`` 128, a window in whole blocks) a
    replicated engine's step runs the ``decode_attention`` kernel; a
    sharded engine serves the model with the strategy's mesh on its config
    (``tp_mesh``), so its step keeps the head-sharded plain einsums: a bare
    ``pallas_call`` is refused on a stack GSPMD has sharded. Tokens equal
    ``generate()``'s either way."""
    from pytorch_distributed_training_tutorials_tpu.ops import (
        decode_attention as kernel_module,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, max_seq_len=128,
        scan_layers=True,
    )
    model, params = _make(cfg)
    reqs = [(_prompt(8150 + i, p), m) for i, (p, m) in enumerate(REQS[:3])]
    calls = []
    real = kernel_module.decode_attention
    monkeypatch.setattr(
        kernel_module, "decode_attention",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1],
    )
    eng_r, out_r = _run_stream(model, params, reqs)
    assert calls and eng_r.model.cfg.tp_mesh is None
    del calls[:]
    strat = _tp(2)
    eng_t, out_t = _run_stream(model, params, reqs, strategy=strat)
    assert not calls and eng_t.model.cfg.tp_mesh is strat.mesh
    for (p, m), r, t in zip(reqs, out_r, out_t):
        assert r.tokens == t.tokens == _reference(model, params, p, m)
    kv = _kv_leaf(eng_t)
    assert {s.data.shape for s in kv.addressable_shards} == {
        (2, 2, 128, 1, 128)
    }


def test_tp2_fetch_budget_and_zero_recompile(model_params, monkeypatch):
    """Sharding must not change the fetch discipline: one batched fetch
    per chain + one scalar per prefill at tp=2, and a second wave of
    requests reuses the warm compiled programs (zero recompiles)."""
    model, params = model_params
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, strategy=_tp(2)
    )
    prompts = [_prompt(8200 + i, 4 + 3 * i) for i in range(3)]
    wave2 = [_prompt(8300 + i, 5) for i in range(2)]
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=20))
    completions = engine.run_until_idle()
    assert len(completions) == 3
    assert calls["n"] == engine.n_chains + engine.n_prefills
    n_chain = engine._chain._cache_size()
    n_prefill = engine._prefill._cache_size()
    assert n_chain == 1
    # second wave, same prompt buckets: nothing recompiles
    for p in wave2:
        engine.submit(Request(prompt=p, max_new_tokens=6))
    assert len(engine.run_until_idle()) == 2
    assert engine._chain._cache_size() == n_chain == 1
    assert engine._prefill._cache_size() == n_prefill
    assert calls["n"] == engine.n_chains + engine.n_prefills


def test_tp2_decode_hlo_all_reduce_only(model_params):
    """The compiled decode chain at tp=2 contains all-reduces ONLY (the
    Megatron forward's o_proj/down_proj/logit reductions) — any
    all-gather / reduce-scatter / all-to-all means a cache leaf or
    activation got resharded mid-decode."""
    model, params = model_params
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, strategy=_tp(2)
    )
    rep = engine.audit_decode_hlo()
    assert rep["ok"], rep["problems"][:3]
    assert set(rep["collectives"]) == {"all-reduce"}
    assert rep["collectives"]["all-reduce"] > 0
    stats = engine.tp_stats()
    assert stats["tp_hlo_ok"] is True
    assert stats["tp_collectives"] == rep["collectives"]["all-reduce"]


# ------------------------------------------------- layouts + composition

@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        pytest.param(dict(kv_cache_dtype="int8"), marks=pytest.mark.slow),
    ],
    ids=["scan_layers", "gqa", "int8kv"],
)
def test_tp4_token_exact_layouts(cfg_kwargs):
    """tp=4 across the scanned (leading layer axis), GQA (kv_heads=2
    does NOT divide tp=4 — the cache degenerates replicated while q
    stays sharded), and int8-KV (scales shard with their K/V) layouts:
    engine-vs-engine token-exact on the staggered stream."""
    model, params = _make(dataclasses.replace(CFG, **cfg_kwargs))
    reqs = [(_prompt(8400 + i, p), m) for i, (p, m) in enumerate(REQS[:4])]
    _, out_r = _run_stream(model, params, reqs)
    _, out_t = _run_stream(model, params, reqs, strategy=_tp(4))
    assert [c.tokens for c in out_t] == [c.tokens for c in out_r]


@pytest.mark.slow
def test_tp2_composed_full_stack(model_params):
    """The everything-composed pin: tp=2 under prefix cache + n-gram
    speculation + multi-tenant adapters + paged KV + depth-2 pipelining
    with chunked prefill is token-exact to the identical composition on
    the replicated engine, with the summed fetch budget (chains +
    prefills + splices) intact on the sharded side."""
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.adapters import AdapterBank

    model, params = model_params
    bank = AdapterBank(model, n_adapters=4, rank=4)
    for t in (1, 2):
        rng = np.random.Generator(np.random.PCG64(1000 + t))
        bank.register(f"tenant-{t}", jax.tree_util.tree_map(
            lambda leaf: jnp.asarray(
                rng.standard_normal(leaf.shape) * 0.05, leaf.dtype
            ),
            bank.row_zeros(),
        ))
    # shared-prefix stream so splices actually fire
    rng = np.random.Generator(np.random.PCG64(42))
    shared = rng.integers(0, CFG.vocab_size, (14,)).tolist()
    reqs = []
    for i in range(8):
        p_len = (6, 10, 14)[i % 3]
        k = int(round(0.7 * p_len))
        tail = rng.integers(0, CFG.vocab_size, (p_len - k,)).tolist()
        reqs.append((shared[:k] + tail, 5 + (i % 3)))
    kw = dict(
        n_slots=2, tokens_per_launch=8, prefix_cache_bytes=16 * 1024 * 1024,
        speculative_k=2, adapter_bank=bank, pipeline_depth=2,
        prefill_chunk=8, paged=True, page_size=8, pool_pages=16,
    )

    def run(**extra):
        engine = ServeEngine(model, params, **kw, **extra)
        calls = {"n": 0}
        real_get = jax.device_get

        def counting(x):
            calls["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            ids = [
                engine.submit(Request(prompt=p, max_new_tokens=m, seed=i,
                                      adapter=(i % 3) % 2 + 1 if i % 3
                                      else 0))
                for i, (p, m) in enumerate(reqs)
            ]
            out = {c.request_id: c for c in engine.run_until_idle()}
        finally:
            jax.device_get = real_get
        return engine, [out[r].tokens for r in ids], calls["n"]

    eng_t, toks_t, fetches_t = run(strategy=_tp(2))
    _, toks_r, _ = run()
    assert toks_t == toks_r
    assert fetches_t == (
        eng_t.n_chains + eng_t.n_prefills + eng_t.n_splices
    )


@pytest.mark.slow
def test_tp2_paged_kernel_token_exact(model_params):
    """ISSUE 17 x ISSUE 15: the fused page-walk read path under tp=2 is
    token-exact to the replicated gather engine on the oversubscribed
    paged stream. (On the CPU mesh the interpret-mode kernel lowers to
    plain HLO, so GSPMD shards it like the gather twin; a real-chip TP
    deployment of the kernel itself is a shard_map follow-up — the
    per-kv-head grid axis is embarrassingly parallel.)"""
    model, params = model_params
    reqs = [(_prompt(870 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (2, 17)]
    )]
    kw = dict(paged=True, page_size=8, pool_pages=6)
    _, out_r = _run_stream(model, params, reqs, **kw)
    _, out_k = _run_stream(model, params, reqs, strategy=_tp(2),
                           paged_kernel=True, **kw)
    assert [c.tokens for c in out_k] == [c.tokens for c in out_r]


def test_tp2_int8_layers_keep_the_slice_under_the_scan():
    """Int8 weights under ``cfg.tp_mesh``: a replicated scanned model hands
    ``int8_matmul`` the stacked weights and the layer's index (ISSUE 33);
    with the mesh on its config every layer keeps ``lax.scan``'s slice and
    runs ``int8_matmul_tp`` on it (its ``shard_map`` splits a (k, n)
    weight; a bare ``pallas_call`` cannot sit on a stack GSPMD has
    sharded), at widths where the stack would otherwise be read in place.
    The sharded logits are the replicated ones up to the row-parallel
    layers' regrouped activation rounding."""
    import numpy as np
    from helpers import pallas_operands

    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        place_int8_lm_params,
        quantize_lm_params,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=2, d_ff=256,
        max_seq_len=32, scan_layers=True,
    )
    model, params = _make(cfg)
    qparams = quantize_lm_params(params)
    mesh = create_mesh({"data": 4, "model": 2})
    tokens = jnp.asarray([_prompt(9100, 8), _prompt(9101, 8)], jnp.int32)

    def forms(model, p):
        """First-operand dtype of every pallas_call, scan bodies and
        shard_map bodies included."""
        return [ops[0][0] for ops in pallas_operands(
            lambda p: model.apply({"params": p}, tokens), p)]

    rep = TransformerLM(dataclasses.replace(cfg, quantized=True))
    tp = TransformerLM(dataclasses.replace(cfg, quantized=True, tp_mesh=mesh))
    placed = place_int8_lm_params(qparams, mesh)
    assert forms(rep, qparams).count("int32") == 7  # the scanned layers'
    assert forms(tp, placed) and "int32" not in forms(tp, placed)
    want = jax.jit(lambda p: rep.apply({"params": p}, tokens))(qparams)
    got = jax.jit(lambda p: tp.apply({"params": p}, tokens))(placed)
    spread = float(jnp.std(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=0.15 * spread)
