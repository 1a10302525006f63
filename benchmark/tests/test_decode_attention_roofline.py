"""``decode_attention_roofline.*`` on traces made here in the layout the
TPU's profiler writes: ``prog:chain_dispatch`` spans with the rows the
program counted (``kv_rows``, ``ring_rows``: ISSUE 38), their
``prog:chain_fetch``, and ``decode_attention`` calls of ``jit__chain_fn``
whose instructions are the chat cell's (recorded on the chip, PR 33) and the
Phi cell's (a KV pair's rows together, on a ring and on the shared cache);
and the traces the parent's program writes, which carry no count."""

import json
import os

import pytest

from benchmark.lib import decode_roofline, harness, xplane
from benchmark.tests import test_program_trace
from benchmark.tests.test_int8_matmul_stacked_roofline import DECODE_ATTENTION, TAIL
from benchmark.tests.test_program_trace import SPEC, T, bundle_for, read

RECORDED = test_program_trace.HERE  # where the repo's trace texts lie
PEAKS = harness.read_json(os.path.join(harness.BENCH, "peaks.json"))["devices"]["TPU v5 lite"]
METRICS = ["decode_attention_roofline.latency", "decode_attention_roofline.serve"]
CHAIN, PREFILL = 222, 111
PATH = "jit(_chain_fn)/while/body/closed_call/TransformerLM/layer_scan/while/body/" \
    "closed_call/layers/{}/decode_attn/decode_attention/pallas_call:"
FULL = PATH.format("block/attn")
RING = PATH.format("window_block/attn/window_attn")
SHARED = PATH.format("cross_block/attn/shared_kv_attn")


def _phi(stack):
    """A Phi-cell call: 40 bfloat16 query heads padded to a pair's 128,
    ten KV pairs, a pair's rows together."""
    s = f"bf16[{stack}]{{4,3,2,1,0:T(8,128)(2,1)}}"
    return (f"%decode_attention.27 = bf16[64,40,128]{{2,1,0:T(8,128)(2,1)}} custom-call("
            f"s32[1]{{0}} %l, s32[64]{{0}} %p, s32[64]{{0}} %s, s32[64]{{0}} %h, "
            f"bf16[64,40,128]{{2,1,0:T(8,128)(2,1)}} %q, {s} %k, {s} %v)" + TAIL)


PHI_RING, PHI_SHARED = _phi("8,64,10,512,128"), _phi("1,64,10,4096,128")
LATENT = ("%latent_decode_attention.55 = f32[64,128,640]{2,1,0} custom-call(s32[1]{0} %l, "
          "s32[64]{0} %p, f32[64,128,640]{2,1,0} %q, bf16[7,64,4096,640]{3,2,1,0} %c)" + TAIL)


def _text(value):
    return json.dumps(value)  # a proto string literal: quotes escaped


def xspace(ops, spans):
    """The text of an XSpace: ``ops`` [(instruction, start us, us, tf_op
    path, program id)] on ``/device:TPU:0``, ``spans`` [(name, start us,
    us, fields)] on the host's ``python3`` thread; times from 1000 ns."""
    ps = lambda us: int(round(us * 1e6))  # noqa: E731
    out = ['planes {\n id: 1\n name: "/device:TPU:0"']
    out.append(' lines {\n  id: 1\n  name: "XLA Modules"\n  timestamp_ns: 1000')
    out += [f"  events {{ metadata_id: {k} offset_ps: 0 duration_ps: 1 }}" for k in (1, 2)]
    out.append(' }\n lines {\n  id: 2\n  name: "XLA Ops"\n  timestamp_ns: 1000')
    out += [f"  events {{ metadata_id: {10 + i} offset_ps: {ps(s)} duration_ps: {ps(d)} }}"
            for i, (_, s, d, _, _) in enumerate(ops)]
    out.append(" }")
    for k, name in ((1, f"jit__prefill_fn({PREFILL})"), (2, f"jit__chain_fn({CHAIN})")):
        out.append(f" event_metadata {{ key: {k} value {{ id: {k} name: {_text(name)} }} }}")
    for i, (name, _, _, path, program) in enumerate(ops):
        stats = (f"stats {{ metadata_id: 1 str_value: {_text(path)} }} " if path else "")
        stats += f"stats {{ metadata_id: 2 uint64_value: {program} }}"
        out.append(f" event_metadata {{ key: {10 + i} value {{ id: {10 + i} "
                   f"name: {_text(name)} {stats} }} }}")
    for k, name in ((1, "tf_op"), (2, "program_id")):
        out.append(f' stat_metadata {{ key: {k} value {{ id: {k} name: "{name}" }} }}')
    out.append('}\nplanes {\n id: 2\n name: "/host:CPU"')
    out.append(' lines {\n  id: 1\n  name: "python3"\n  timestamp_ns: 1000')
    fields = sorted({f for *_, fs in spans for f in fs})
    for i, (_, s, d, fs) in enumerate(spans):
        stats = " ".join(f"stats {{ metadata_id: {fields.index(f) + 1} int64_value: {v} }}"
                         for f, v in fs.items())
        out.append(f"  events {{ metadata_id: {i + 1} offset_ps: {ps(s)} "
                   f"duration_ps: {ps(d)} {stats} }}")
    out.append(" }")
    for i, (name, *_) in enumerate(spans):
        out.append(f" event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
                   f'name: "prog:{name}" }} }}')
    for k, f in enumerate(fields, 1):
        out.append(f' stat_metadata {{ key: {k} value {{ id: {k} name: "{f}" }} }}')
    out.append("}")
    return "\n".join(out)


def traced(tmp_path, monkeypatch, ops, spans, cell="serve-internlm2-1.8b-chat",
           window=(T(10), T(1000))):
    """What ``run.py`` hands a reader after a traced run of ``cell``."""
    name = f"t{len(list(tmp_path.iterdir()))}.txt"
    here = tmp_path / "texts"
    here.mkdir(exist_ok=True)
    (here / name).write_text(xspace(ops, spans))
    monkeypatch.setattr(test_program_trace, "HERE", str(here))
    return _with_cell(bundle_for(tmp_path / name[:-4], monkeypatch, name, window), cell)


def _with_cell(b, cell="serve-internlm2-1.8b-chat"):
    b.update(cell=harness.Cell(cell), peaks=PEAKS, root=harness.ROOT)
    return b


def chain(n, start, end, **rows):
    """A chain's dispatch at ``start`` and its fetch ending at ``end``."""
    return [("chain_dispatch", start, 5, {"chain": n, "occupancy": 2, **rows}),
            ("chain_fetch", end - 20, 20, {"chain": n})]


def bound(line, rows):
    """The least seconds for ``rows`` read by the call ``line``."""
    call, = xplane.custom_calls([xplane.Event(line, 0, 1)], 0, 2)
    q = next(o for o in call.operands if len(o[1]) == 3 and o[0] != "s32")
    k = next(o for o in call.operands if len(o[1]) == 5)
    size = {"bf16": 2, "f32": 4}
    cost = harness.kernel_cost(harness.ROOT, "decode_attention").cost
    slots, heads, d = q[1]
    ops, byts = cost(rows, slots, heads, min(k[1][2:4]), d, size[k[0]], size[q[0]],
                     size[call.results[0][0]])
    return max(ops / PEAKS["flops_per_s"]["bfloat16"], byts / PEAKS["hbm_bytes_per_s"])


STEPS = 8  # tokens_per_launch of the chat and Phi cells


@pytest.mark.parametrize("metric", METRICS)
def test_a_call_at_its_bound_reads_100(metric, tmp_path, monkeypatch):
    rows = 1600 * STEPS
    took = bound(DECODE_ATTENTION, rows / STEPS) * 1e6  # us
    b = traced(tmp_path, monkeypatch, [(DECODE_ATTENTION, 110, took, FULL, CHAIN)],
               chain(3, 100, 400, kv_rows=rows))
    # to the profiler's nanosecond
    assert read(metric, b) == pytest.approx(100.0, rel=1e-3)
    # twice the time: half the share
    b = traced(tmp_path, monkeypatch, [(DECODE_ATTENTION, 110, 2 * took, FULL, CHAIN)],
               chain(3, 100, 400, kv_rows=rows))
    assert read(metric, b) == pytest.approx(50.0, rel=1e-3)


def test_calls_are_matched_to_their_chain_by_chain(tmp_path, monkeypatch):
    """Two chains whose fields differ; a call between them, a prefill's
    call, another kernel and a fetch whose dispatch the trace lacks count
    for nothing."""
    ops = [(DECODE_ATTENTION, 110, 30, FULL, CHAIN),
           (DECODE_ATTENTION, 150, 30, FULL, CHAIN),
           (DECODE_ATTENTION, 450, 30, FULL, CHAIN),  # between the chains
           (DECODE_ATTENTION, 520, 30, FULL, PREFILL),  # not the chain's program
           (LATENT, 560, 30, FULL, CHAIN),
           (DECODE_ATTENTION, 610, 30, FULL, CHAIN)]
    spans = (chain(8, 600, 700, kv_rows=16 * STEPS) + chain(7, 100, 400, kv_rows=800 * STEPS)
             + [("chain_fetch", 880, 20, {"chain": 9})])
    b = traced(tmp_path, monkeypatch, ops, spans)
    got = [(c.event.start, rows, ring) for c, rows, ring in decode_roofline.calls(b)]
    assert got == [(T(110), 800, False), (T(150), 800, False), (T(610), 16, False)]
    want = sum(bound(DECODE_ATTENTION, r) for r in (800, 800, 16)) / 90e-6
    assert read("decode_attention_roofline.latency", b) == pytest.approx(100 * want, rel=1e-3)


def test_a_chain_dispatched_before_the_trace_is_dropped(tmp_path, monkeypatch):
    """Its calls may be in the window; its dispatch is not, so its count
    is not what they read."""
    ops = [(DECODE_ATTENTION, 20, 30, FULL, CHAIN), (DECODE_ATTENTION, 210, 30, FULL, CHAIN)]
    spans = chain(4, 5, 100, kv_rows=99 * STEPS) + chain(5, 200, 300, kv_rows=50 * STEPS)
    b = traced(tmp_path, monkeypatch, ops, spans, window=(T(10), T(1000)))
    assert [(c.event.start, r) for c, r, _ in decode_roofline.calls(b)] == [(T(210), 50)]
    assert read("decode_attention_roofline.latency", b) == pytest.approx(
        100 * bound(DECODE_ATTENTION, 50) / 30e-6, rel=1e-3)
    # the window opening after the second dispatch leaves nothing to read
    b = traced(tmp_path, monkeypatch, ops, spans, window=(T(202), T(1000)))
    assert read("decode_attention_roofline.latency", b) is None


def test_ring_calls_take_ring_rows(tmp_path, monkeypatch):
    """The Phi cell: a call under ``window_attn`` is a ring's and takes
    ``ring_rows``; the shared cache's calls take ``kv_rows``. The share
    apart by kind, and together."""
    ops = [(PHI_RING, 110, 200, RING, CHAIN), (PHI_SHARED, 320, 800, SHARED, CHAIN),
           (PHI_SHARED, 1130, 800, SHARED, CHAIN)]
    rows = dict(kv_rows=64 * 1500 * STEPS, ring_rows=64 * 512 * STEPS)
    b = traced(tmp_path, monkeypatch, ops, chain(2, 100, 2000, **rows),
               cell="serve-phi-4-mini-flash-reasoning", window=(T(10), T(3000)))
    got = [(rows, ring) for _, rows, ring in decode_roofline.calls(b)]
    assert got == [(64 * 512, True), (64 * 1500, False), (64 * 1500, False)]
    ring, full = bound(PHI_RING, 64 * 512), bound(PHI_SHARED, 64 * 1500)
    assert decode_roofline.share(b, ring=True) == pytest.approx(100 * ring / 200e-6, rel=1e-3)
    assert decode_roofline.share(b, ring=False) == pytest.approx(100 * full / 800e-6, rel=1e-3)
    assert read("decode_attention_roofline.serve", b) == pytest.approx(
        100 * (ring + 2 * full) / 1800e-6, rel=1e-3)
    # a chain without ring_rows leaves its ring calls out
    b = traced(tmp_path, monkeypatch, ops, chain(2, 100, 2000, kv_rows=rows["kv_rows"]),
               cell="serve-phi-4-mini-flash-reasoning", window=(T(10), T(3000)))
    assert [r for _, _, r in decode_roofline.calls(b)] == [False, False]


@pytest.mark.parametrize("metric", METRICS)
def test_the_parents_traces_read_none(metric, tmp_path, monkeypatch):
    """Spans without the count (the parent's program), the hand-made trace
    and PR 26's recorded one, no trace at all: the line leaves the metric
    out, and nothing raises."""
    ops = [(DECODE_ATTENTION, 110, 30, FULL, CHAIN)]
    b = traced(tmp_path, monkeypatch, ops, chain(3, 100, 400))
    assert read(metric, b) is None
    assert read(metric, {"trace": None}) is None
    monkeypatch.setattr(test_program_trace, "HERE", RECORDED)
    for name in ("program_trace.txt", "recorded_trace.txt"):
        (tmp_path / name).mkdir()
        assert read(metric, _with_cell(bundle_for(tmp_path / name, monkeypatch, name))) is None


def test_declared_as_the_issue_asks():
    """Layer ``kernels``, the program's count, and the cells whose chains
    run ``decode_attention`` (openPangu's run ``latent_decode_attention``)."""
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    lat, srv = (declared[m] for m in METRICS)
    assert lat["workloads"] == ["serve-internlm2-1.8b-chat"] and lat["moves"] == "tpot_p95_ms"
    assert srv["workloads"] == ["serve-mistral-7b-long", "serve-phi-4-mini-flash-reasoning",
                                "serve-falcon-h1-34b-reasoning"]
    assert srv["moves"] == "tpot_mean_ms"
    for m in (lat, srv):
        assert (m["layer"], m["unit"], m["better"], m["source"]) == (
            "kernels", "%", "higher", "program_counter")
    assert [m["name"] for m in SPEC["per_layer"][-2:]] == METRICS  # appended
