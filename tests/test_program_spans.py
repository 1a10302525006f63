"""The program's host spans on the profiler's clock (ISSUE 27).

``ServeEngine`` and ``Trainer`` wrap their phases in
``utils.profiling.annotate`` spans (``prog:<phase>``, integer fields)
unconditionally. Pinned here on the CPU: a toy engine and a toy trainer run
under ``jax.profiler.start_trace``, the ``.xplane.pb`` is read back with
``jax.profiler.ProfileData`` and

- every span of the issue's table is there with its fields,
- children lie inside their parents on one thread,
- ``refill`` / ``prefill_fetch`` / ``complete`` of one request share its
  ``rid``; a chain has one ``chain_dispatch`` and one ``chain_fetch`` with
  equal ``chain``,
- an engine with no profiler running serves byte-identical tokens,
- a plain chain's ``kv_rows`` (and ``ring_rows`` where the cache holds a
  ring) equal the rows its decode attention really attended, recorded by
  a test-only ``jax.debug.callback`` on the depths the attention was
  handed (ISSUE 38); paged and speculative chains carry neither.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu import create_mesh
from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.models import MLP, sambay
from pytorch_distributed_training_tutorials_tpu.models import (
    transformer as transformer_mod,
)
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.obs.flight import EVENT_KINDS
from pytorch_distributed_training_tutorials_tpu.serve import (
    Request,
    ServeEngine,
)
from pytorch_distributed_training_tutorials_tpu.serve import (
    engine as engine_mod,
)
from pytorch_distributed_training_tutorials_tpu.serve.slots import bucket_len
from pytorch_distributed_training_tutorials_tpu.train import Trainer
from pytorch_distributed_training_tutorials_tpu.utils import profiling
from tests.helpers import make_cls_dataset

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=64,
    scan_layers=True,
)
REQS = [(3, 9), (7, 12), (5, 5), (12, 6), (2, 1)]

# span -> the fields it must carry, from the issue's table
ENGINE_SPANS = {
    "submit": {"rid", "p_len", "max_new"},
    "step": {"chain"},
    "sweep": set(),
    "queue_pop": {"rid"},
    "refill": {"rid", "slot"},
    # ``bucket`` sits where a prefill picked it: the padded length of the
    # launch whose first token this fetch waits for
    "prefill_fetch": {"rid", "bucket"},
    # ``kv_rows``: the rows a chain's decode attention reads (ISSUE 38)
    "chain_dispatch": {"chain", "occupancy", "kv_rows"},
    "chain_fetch": {"chain"},
    "distribute": {"chain", "tokens"},
    "complete": {"rid", "tokens"},
}
PARENTS = {
    "sweep": "step", "queue_pop": "step", "refill": "step",
    "prefill_fetch": "refill", "chain_dispatch": "step",
    "chain_fetch": "step", "distribute": "step",
}
TRAINER_SPANS = {"loader_next": {"step"}, "dispatch": {"step"},
                 "epoch_sync": {"step"}}


def _prompt(seed, n):
    return jax.device_get(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, CFG.vocab_size)
    ).tolist()


def _serve(model, params):
    """Five staggered requests through two slots; tokens by request."""
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=4)
    pending = [
        Request(prompt=_prompt(100 + i, p), max_new_tokens=n)
        for i, (p, n) in enumerate(REQS)
    ]
    out = {}
    while pending or not engine.idle:
        if pending:
            engine.submit(pending.pop(0))
        for c in engine.step():
            out[c.request_id] = (c.tokens, c.finish_reason)
    return out


def _spans(logdir):
    """[(name, start, end, fields, thread)] of every ``prog:`` span."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(profiling.SPAN_PREFIX):
                    start = int(ev.start_ns)
                    out.append((
                        name[len(profiling.SPAN_PREFIX):], start,
                        start + int(ev.duration_ns),
                        {str(k): v for k, v in ev.stats},
                        (str(plane.name), str(line.name)),
                    ))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(span, spans):
    """The innermost other span on the thread that encloses ``span``."""
    around = [s for s in spans if s is not span and s[4] == span[4]
              and s[1] <= span[1] and span[2] <= s[2]]
    return min(around, key=lambda s: s[2] - s[1]) if around else None


@pytest.fixture(scope="module")
def model_params():
    model = TransformerLM(CFG)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def engine_trace(model_params, tmp_path_factory):
    model, params = model_params
    untraced = _serve(model, params)  # compiles; no profiler running
    logdir = str(tmp_path_factory.mktemp("engine_trace"))
    with profiling.trace(logdir):
        traced = _serve(model, params)
    return untraced, traced, _spans(logdir)


def test_tokens_do_not_depend_on_the_profiler(engine_trace):
    untraced, traced, _ = engine_trace
    assert len(untraced) == len(REQS)
    assert traced == untraced


@pytest.mark.parametrize("name", sorted(ENGINE_SPANS))
def test_engine_span_present_with_fields_and_parent(engine_trace, name):
    spans = engine_trace[2]
    mine = [s for s in spans if s[0] == name]
    assert mine, f"no prog:{name} span in the trace"
    for s in mine:
        assert set(s[3]) == ENGINE_SPANS[name], (name, s[3])
        assert all(isinstance(v, int) for v in s[3].values())
        if name in PARENTS:
            parent = _parent(s, spans)
            assert parent is not None and parent[0] == PARENTS[name], (
                name, parent and parent[0])
    assert len({s[4] for s in mine}) == 1  # one thread: nesting is lexical


def test_spans_are_named_after_flight_kinds_where_one_exists():
    shared = {"submit", "queue_pop", "sweep", "complete"}
    assert shared <= EVENT_KINDS and shared <= set(ENGINE_SPANS)


def test_spans_of_one_request_share_its_rid(engine_trace):
    _, traced, spans = engine_trace
    by = lambda name: {  # noqa: E731
        s[3]["rid"]: s for s in spans if s[0] == name and s[3]["rid"] >= 0}
    submits, pops, refills = by("submit"), by("queue_pop"), by("refill")
    fetches, completes = by("prefill_fetch"), by("complete")
    assert set(submits) == set(traced) == set(refills) == set(completes)
    assert set(pops) == set(fetches) == set(traced)
    for rid, (tokens, _) in traced.items():
        assert completes[rid][3]["tokens"] == len(tokens)
        assert _parent(fetches[rid], spans) is refills[rid]
        assert refills[rid][3]["slot"] in (0, 1)
        p_len = REQS[rid][0]
        assert fetches[rid][3]["bucket"] == bucket_len(p_len, CFG.max_seq_len)
        assert submits[rid][2] <= pops[rid][1] <= refills[rid][1]
        assert refills[rid][1] <= completes[rid][1]
    # a request that completes at its prefill completes inside its refill
    one = next(r for r, (t, _) in traced.items() if len(t) == 1)
    assert _parent(completes[one], spans) is refills[one]


def test_one_dispatch_and_one_fetch_a_chain(engine_trace):
    spans = engine_trace[2]
    dispatched = [s[3]["chain"] for s in spans if s[0] == "chain_dispatch"]
    fetched = [s[3]["chain"] for s in spans if s[0] == "chain_fetch"]
    handed = [s[3]["chain"] for s in spans if s[0] == "distribute"]
    assert dispatched and sorted(dispatched) == sorted(set(dispatched))
    assert dispatched == fetched == handed
    total = sum(s[3]["tokens"] for s in spans if s[0] == "distribute")
    # every token but each request's first comes out of a chain
    assert total == sum(len(t) - 1 for t, _ in engine_trace[1].values())
    for s in spans:
        if s[0] == "chain_dispatch":
            assert 1 <= s[3]["occupancy"] <= 2
            assert _parent(s, spans)[3]["chain"] == s[3]["chain"]


@pytest.fixture(scope="module")
def trainer_trace(tmp_path_factory):
    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(make_cls_dataset(), 8, mesh, seed=0)
    trainer = Trainer(MLP(features=(32, 4)), loader, optax.adam(1e-3),
                      loss="cross_entropy", seed=0, quiet=True)
    trainer.train(1)  # compiles outside the trace
    logdir = str(tmp_path_factory.mktemp("trainer_trace"))
    with profiling.trace(logdir):
        trainer.train(2)
    return len(loader), _spans(logdir)


@pytest.mark.parametrize("name", sorted(TRAINER_SPANS))
def test_trainer_span_present_with_fields(trainer_trace, name):
    steps, spans = trainer_trace
    mine = [s for s in spans if s[0] == name]
    assert mine and all(set(s[3]) == TRAINER_SPANS[name] for s in mine)
    if name == "dispatch":
        assert [s[3]["step"] for s in mine] == list(range(steps))
    elif name == "loader_next":
        # the loop's own next(): one more than the batches, the last one
        # finding the loader exhausted
        assert [s[3]["step"] for s in mine] == list(range(steps + 1))
    else:
        assert [s[3]["step"] for s in mine] == [steps]


def test_trainer_spans_follow_the_loop(trainer_trace):
    _, spans = trainer_trace
    order = [s[0] for s in spans]
    assert order[:4] == ["loader_next", "dispatch", "loader_next", "dispatch"]
    assert order[-2:] == ["loader_next", "epoch_sync"]
    ends = [s[2] for s in spans]
    assert all(a <= b for a, b in zip(ends, [s[1] for s in spans][1:]))


# -- kv_rows and ring_rows against the rows the attention saw (ISSUE 38) ----

T = 4  # tokens_per_launch of every engine below


@pytest.fixture
def dispatched(monkeypatch):
    """The fields of every ``prog:chain_dispatch`` the engine opens."""
    out = []
    real = engine_mod.annotate

    def spy(name, **fields):
        if name == "chain_dispatch":
            out.append(fields)
        return real(name, **fields)

    monkeypatch.setattr(engine_mod, "annotate", spy)
    return out


@pytest.fixture
def attended(monkeypatch):
    """The depths the plain path's attention was handed, a (slots,) array
    a step of a layer, in the order the device ran them: by cache kind,
    ``kv`` (``Attention``'s cache: layer 0's step alone), ``window_attn``
    (a ring) and ``shared_kv_attn`` (``models/sambay.py``: every layer's
    call, with the rows its stack holds). Test-only ``jax.debug.callback``
    spies on engines built after the patch."""
    seen = {"kv": [], "window_attn": [], "shared_kv_attn": []}
    store, cached = transformer_mod._store_decode_kv, sambay._cached_attention

    def on_store(var, val, pos, layer=None, heads_major=False):
        if var.name == "cached_key" and val.shape[1] == 1 and pos.ndim == 1:
            def note(p, first):
                if first:
                    seen["kv"].append((np.array(p), CFG.max_seq_len))
            jax.debug.callback(note, pos, jnp.asarray(
                True if layer is None else layer == 0))
        return store(var, val, pos, layer, heads_major)

    def on_cached(q_pad, k_stack, v_stack, layer, depth, scope):
        rows = k_stack.shape[3]
        jax.debug.callback(
            lambda d: seen[scope].append((np.array(d), rows)), depth)
        return cached(q_pad, k_stack, v_stack, layer, depth, scope)

    monkeypatch.setattr(transformer_mod, "_store_decode_kv", on_store)
    monkeypatch.setattr(sambay, "_cached_attention", on_cached)
    return seen


def _attended_by_chain(records, engine):
    """Rows attended a call of each step, summed over a chain's steps and
    slots: the device runs the chains one after the other, each ``T``
    steps of the same calls (a prefill's calls are of one row: left out);
    a depth at or past the rows is a slot that holds nothing, which the
    kernel reads no row of."""
    n_chains = engine.n_chains
    records = [r for r in records if len(r[0]) == engine.n_slots]
    calls = len(records) // (n_chains * T)
    assert calls and calls * n_chains * T == len(records)
    out = []
    for c in range(n_chains):
        mine = records[c * T * calls:(c + 1) * T * calls]
        rows = sum(int(d) + 1 for depth, w in mine for d in depth if d < w)
        assert rows % calls == 0  # every call of a step saw the same depths
        out.append(rows // calls)
    return out


def _drive(engine, reqs, stagger=False):
    """``reqs`` are (prompt length, max_new, eos_token): all submitted at
    once, or one before each step; completions by request."""
    pending, out = list(reqs), {}
    while pending or not engine.idle:
        for p, n, eos in pending[:1] if stagger else pending:
            engine.submit(Request(prompt=_prompt(200 + p, p),
                                  max_new_tokens=n, eos_token=eos))
        pending = pending[1:] if stagger else []
        for c in engine.step():
            out[c.request_id] = c
    return out


def _eos_mid_request(model, params):
    """A token request 0 samples for the first time at its third token or
    later: as its ``eos_token`` it parks the slot with budget left."""
    first = _drive(ServeEngine(model, params, n_slots=2, tokens_per_launch=T),
                   [(6, 12, None), (4, 10, None)])
    toks = first[0].tokens
    return next(t for k, t in enumerate(toks) if k >= 2 and t not in toks[:k])


KV_CASES = {
    # five depths through three slots: a budget that ends mid-chain (10, 6),
    # one that ends at the first step (5, 2), refills as slots free
    "depths_and_budgets": (
        dict(n_slots=3), [(3, 9), (10, 6), (5, 2), (12, 7), (2, 11)], False),
    # request 0 stops at an EOS with budget left; its slot is parked
    "eos_parks_with_budget": (dict(n_slots=2), [(6, 12), (4, 10)], False),
    # a request arrives before every step: refilled between two chains
    "refill_between_chains": (
        dict(n_slots=2), [(3, 6), (9, 5), (4, 9), (7, 3)], True),
    # a chain dispatched before the last one is fetched: the depths the
    # device reached in flight
    "pipelined": (
        dict(n_slots=2, pipeline_depth=2), [(3, 9), (8, 6), (5, 7)], False),
}


@pytest.mark.parametrize("case", sorted(KV_CASES))
def test_kv_rows_are_the_rows_the_attention_attended(
        case, model_params, dispatched, attended):
    model, params = model_params
    kw, reqs, stagger = KV_CASES[case]
    eos = _eos_mid_request(model, params) if case.startswith("eos") else None
    reqs = [(p, n, eos if i == 0 else None) for i, (p, n) in enumerate(reqs)]
    attended["kv"].clear()
    dispatched.clear()
    engine = ServeEngine(model, params, tokens_per_launch=T, **kw)
    done = _drive(engine, reqs, stagger)
    assert len(done) == len(reqs)
    assert [f["chain"] for f in dispatched] == list(range(engine.n_chains))
    assert all(set(f) == {"chain", "occupancy", "kv_rows"} for f in dispatched)
    want = _attended_by_chain(attended["kv"], engine)
    assert [f["kv_rows"] for f in dispatched] == want
    assert want[0] > 0
    if eos is not None:
        assert done[0].finish_reason == "eos"
        assert len(done[0].tokens) < reqs[0][1]


RING_CFG = TransformerConfig(
    vocab_size=64, d_model=128, n_layers=8, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=32, mb_per_layer=2, sliding_window=8,
    tie_embeddings=True, scan_layers=True,
)


@pytest.mark.parametrize("reqs", [
    [(3, 9), (5, 8)],  # prompts inside the ring of 8, answers past it
    [(12, 6), (20, 9)],  # prompts longer than the ring
    [(3, 9), (12, 6), (7, 2)],  # both, and a refill
], ids=["shorter", "longer", "both"])
def test_ring_rows_are_the_rows_the_rings_attended(reqs, dispatched, attended):
    """``models/sambay.py``: ``kv_rows`` counts the shared cache's reads
    (layer ``half + 1`` and the cross layers), ``ring_rows`` a window
    layer's; the ring's length is its leaf's, 8 rows."""
    model = TransformerLM(RING_CFG)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=T)
    assert engine._ring == RING_CFG.sliding_window
    done = _drive(engine, [(p, n, None) for p, n in reqs])
    assert len(done) == len(reqs)
    assert all(set(f) == {"chain", "occupancy", "kv_rows", "ring_rows"}
               for f in dispatched)
    assert [f["kv_rows"] for f in dispatched] == _attended_by_chain(
        attended["shared_kv_attn"], engine)
    ring = _attended_by_chain(attended["window_attn"], engine)
    assert [f["ring_rows"] for f in dispatched] == ring
    # a ring caps what a slot reads: fewer rows than the shared cache's
    # once a sequence outgrows it
    assert sum(ring) < sum(f["kv_rows"] for f in dispatched)


@pytest.mark.parametrize("kw", [
    dict(paged=True, page_size=8, pool_pages=32),
    dict(speculative_k=2),
], ids=["paged", "speculative"])
def test_other_chains_carry_no_rows(kw, model_params, dispatched):
    """Paged and speculative chains read through other kernels by other
    counts: their spans keep the fields they had."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=T, **kw)
    assert len(_drive(engine, [(5, 9, None), (9, 6, None)])) == 2
    assert dispatched
    assert all(set(f) == {"chain", "occupancy"} for f in dispatched)
