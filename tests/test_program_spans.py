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
- an engine with no profiler running serves byte-identical tokens.
"""

import glob
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu import create_mesh
from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.obs.flight import EVENT_KINDS
from pytorch_distributed_training_tutorials_tpu.serve import (
    Request,
    ServeEngine,
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
    "chain_dispatch": {"chain", "occupancy"},
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
